"""``exec/inputs.py``: the binding types and their one owner.

What each kind answers (rows, host bytes, fingerprint, part i of n,
the errors of the operations it does not have), pinned against what
the tuples and their ``if kind ==`` chains answered before them; and
the owner's rules: every rebinding route drops the node's fingerprint
and device entry, ``release()`` forgets all three, an owned table dies
with its node and a user's does not.
"""

import gc
import pickle

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.exec import inputs as I
from dryad_tpu.exec.jobpackage import PACKAGE_VERSION, load_query, pack_query

TEXT = "to be or not to be that is the question " * 5


def _table():
    return {
        "k": np.arange(100, dtype=np.int32) % 7,
        "v": np.arange(100, dtype=np.float32) / 4,
    }


def _bound(ctx, kind, tmp_path):
    """A query of ``ctx`` bound as ``kind``: 800 bytes on the host
    whatever the kind (100 rows of 8, or 50 tokens of 16)."""
    if kind == "host":
        return ctx.from_arrays(_table())
    if kind == "host_cap":
        return ctx.from_arrays(_table(), partition_capacity=16)
    if kind == "host_physical":
        return ctx.from_text(TEXT)
    path = str(tmp_path / "s")
    ctx.to_store(ctx.from_arrays(_table()), path)
    return ctx.from_store(path)


def _rows_of(binding):
    """The rows of a host-kind binding as a sorted list of tuples."""
    if isinstance(binding, I.StoreParts):
        cols = [
            np.concatenate([p[c] for p in binding.parts])
            for c in sorted(binding.parts[0])
        ] if binding.parts else []
    else:
        cols = [np.asarray(binding.arrays[c]) for c in sorted(binding.arrays)]
    return sorted(zip(*[c.tolist() for c in cols]))


# the literals are what ``DryadContext._binding_fp`` and
# ``query_input_bytes`` gave at the parent (4b70b8a) for these tables
@pytest.mark.parametrize("kind, fp", [
    ("host", "3676bf31482c7c31:None"),
    ("host_cap", "3676bf31482c7c31:16"),
    ("host_physical", "51982c1b456d4860"),
    ("store", "ea87a98130ccc0c9"),
])
def test_the_whole_is_what_it_was_and_the_parts_are_the_whole(
    mesh8, tmp_path, kind, fp
):
    ctx = DryadContext(num_partitions_=8)
    q = _bound(ctx, kind, tmp_path)
    binding = ctx.inputs.get(q.node.id)
    assert binding.kind == kind.replace("_cap", "")
    rows = 50 if kind == "host_physical" else 100
    assert binding.rows() == rows
    assert binding.host_bytes() == ctx.query_input_bytes(q) == 800
    assert ctx.inputs.fingerprint(q.node.id) == fp
    for n in (1, 3, 8):
        parts = [binding.part(i, n) for i in range(n)]
        assert all(type(p).kind == binding.kind for p in parts)
        assert sum(p.rows() for p in parts) == rows
        assert sum(p.host_bytes() for p in parts) == 800
        assert sorted(sum((_rows_of(p) for p in parts), [])) == _rows_of(binding)
    # a part is bound without the whole's capacity
    assert getattr(binding.part(0, 2), "cap", None) is None


def test_a_routed_table_hands_out_its_buckets_as_host_tables():
    arrays = {"k": np.arange(10, dtype=np.int32)}
    routed = I.RoutedTable(arrays, np.asarray([0, 3, 3, 10]))
    parts = [routed.part(i, 3) for i in range(3)]
    assert [type(p) for p in parts] == [I.HostTable] * 3
    assert [p.arrays["k"].tolist() for p in parts] == [
        [0, 1, 2], [], list(range(3, 10))]
    assert routed.kind == "host_routed" and routed.packed() is routed


@pytest.mark.parametrize("binding, op, error, text", [
    (I.DeviceTable(object()), "packed", ValueError,
     "cannot pack a query over device-resident bindings"),
    (I.DeviceTable(object()), "part", ValueError,
     "cannot slice binding kind 'device'"),
    (I.ChunkStream(None), "part", ValueError,
     "cannot slice binding kind 'stream'"),
    (I.ChunkStream(None), "lay_out", RuntimeError,
     "a chunk-stream input cannot bind as a device table"),
    (I.ChunkStream(None), "table", RuntimeError,
     "localdebug: unsupported input binding stream"),
    (I.LoopTable({}), "lay_out", RuntimeError, "unknown binding kind table"),
    (I.RoutedTable({}, [0]), "lay_out", RuntimeError,
     "unknown binding kind host_routed"),
], ids=["device-packed", "device-part", "stream-part", "stream-lay_out",
        "stream-table", "table-lay_out", "host_routed-lay_out"])
def test_an_operation_a_kind_does_not_have_raises_what_the_chain_raised(
    binding, op, error, text
):
    args = {"packed": (), "part": (0, 2), "lay_out": (None, None, None),
            "table": (None, None)}[op]
    with pytest.raises(error, match=text):
        getattr(binding, op)(*args)
    # and what costs nothing on the host says so
    assert (binding.rows(), binding.host_bytes(), binding.fingerprint()) == (
        0, 0, None)


def test_bindings_ship_by_reference_in_a_package_of_version_2(mesh8, tmp_path):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(_table())
    blob = pickle.dumps(ctx.inputs.get(q.node.id))
    assert b"dryad_tpu.exec.inputs" in blob and b"HostTable" in blob
    path = str(tmp_path / "q.pkg")
    assert pack_query(q.where(lambda c: c["k"] < 3), path)["version"] == 2
    assert PACKAGE_VERSION == 2
    loaded = load_query(path)
    (binding,) = loaded.ctx.inputs.snapshot().values()
    assert type(binding) is I.HostTable
    assert binding.fingerprint() == "3676bf31482c7c31:None"
    assert sorted(loaded.collect()["k"].tolist()) == sorted(
        k for k in _table()["k"].tolist() if k < 3)


# -- the owner: one place drops what a rebound node had ---------------------

def _warm(ctx, q):
    """Run ``q`` and fingerprint its input: the node then has all three."""
    q.collect()
    ctx.inputs.fingerprint(q.node.id)
    assert ctx.inputs.holds(q.node.id) == (True, True, True)


def _append(ctx, q):
    ctx.append_arrays(q, {"k": np.asarray([1], np.int32),
                          "v": np.asarray([1.0], np.float32)})
    return 101


def _worker_part(ctx, q):
    from dryad_tpu.cluster.worker import _bind_part

    _bind_part(q, ctx.inputs.snapshot(), 1, 4)
    return 25


def _stream_adopt(ctx, q):
    fresh = ctx.from_arrays({c: v[:10] for c, v in _table().items()})
    _warm(ctx, fresh)
    ctx.inputs.move(fresh.node.id, q.node.id)
    assert ctx.inputs.holds(fresh.node.id) == (False, False, False)
    return 10


@pytest.mark.parametrize("route", [_append, _worker_part, _stream_adopt])
def test_a_rebound_node_keeps_no_fingerprint_and_no_device_entry(mesh8, route):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(_table())
    _warm(ctx, q)
    old_fp = ctx.inputs.fingerprint(q.node.id)
    rows = route(ctx, q)
    assert ctx.inputs.holds(q.node.id) == (True, False, False)
    assert ctx.inputs.fingerprint(q.node.id) != old_fp
    assert len(q.collect()["k"]) == rows  # laid out anew, from the new table


def test_a_committed_view_snapshot_forgets_its_state_table(mesh8):
    from dryad_tpu.views import ViewRegistry, finalize_query

    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(_table()).group_by("k", {"s": ("sum", "v")})
    view = ViewRegistry(ctx).register("tenant", q)
    final = finalize_query(view, ctx)
    table = final.collect()
    _version, node_id = view._pending
    ctx.inputs.fingerprint(node_id)
    assert ctx.inputs.holds(node_id) == (True, True, True)
    view.commit_snapshot(table, ctx)
    assert ctx.inputs.holds(node_id) == (False, False, False)


def test_release_forgets_the_fingerprint_too(mesh8):
    ctx = DryadContext(num_partitions_=8)
    cached = ctx.from_arrays(_table()).where(lambda c: c["k"] < 3).cache()
    assert ctx.query_fingerprint(cached) is None  # device-resident: no key
    assert ctx.inputs.holds(cached.node.id) == (True, True, False)
    ctx.release(cached)
    assert ctx.inputs.holds(cached.node.id) == (False, False, False)
    with pytest.raises(RuntimeError, match="has no binding"):
        cached.collect()
    with pytest.raises(ValueError, match="takes the query returned by cache"):
        ctx.release(cached)


def test_rebuild_mesh_forgets_device_tables_and_keeps_host_ones(mesh8):
    import jax

    ctx = DryadContext(num_partitions_=4)
    q = ctx.from_arrays(_table())
    cached = q.cache()
    _warm(ctx, q)
    ctx.rebuild_mesh([jax.devices()[3].id])
    assert ctx.inputs.holds(cached.node.id) == (False, False, False)
    assert ctx.inputs.holds(q.node.id) == (True, True, False)
    assert ctx.inputs.staging.held_bytes() == 0
    assert len(q.collect()["k"]) == 100


@pytest.mark.parametrize("owned", [True, False])
def test_an_owned_table_dies_with_its_node_and_a_users_does_not(mesh8, owned):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_text(TEXT) if owned else ctx.from_arrays(_table())
    node_id = q.node.id
    _warm(ctx, q)
    del q
    gc.collect()
    # the device entry is the LRU's own and goes by its budget alone
    assert ctx.inputs.holds(node_id) == (not owned, not owned, True)
    assert (ctx.inputs.get(node_id) is None) == owned


def test_a_job_that_ingested_releases_twice_and_a_requery_never(mesh8):
    ctx = DryadContext(num_partitions_=8)
    q = ctx.from_arrays(_table()).order_by(["k"])

    def names():
        return [e["name"] for e in ctx.events.events() if e["kind"] == "span"]

    q.collect()
    fresh = names()
    assert fresh.count("bind") == 1 and fresh.count("release") == 2
    assert fresh.index("bind") < fresh.index("release") < fresh.index("fetch_wait")
    assert fresh[-2:] == ["release", "collect"]  # spans close inside out
    q.collect()
    again = names()[len(fresh):]
    assert "bind" not in again and "release" not in again
