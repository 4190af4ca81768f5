"""The deployment ``gensort-100b-1c`` as its cell runs it, on the CPU
mesh: ``benchmarks/jobs/sort_100b.py`` loaded by path (NumPy alone: it
imports nothing of the program), its ``make_table`` / ``compare`` /
``control`` at 2^12 rows, and its ``bind(...)`` collected fresh and
again through ``DryadContext`` at P = 1 and 4 on the keys that stress a
multi-word compare, against the job's own reference and compare."""

import importlib.util
import json
import os

import numpy as np
import pytest

from dryad_tpu import BYTES, DryadContext

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 1 << 12
PARAMS = {"rows": ROWS}
NUMBERS = {"sort100b.rows_missing", "sort100b.keys_out_of_order",
           "sort100b.payloads_off_key"}


@pytest.fixture(scope="module")
def job():
    path = os.path.join(ROOT, "benchmarks", "jobs", "sort_100b.py")
    spec = importlib.util.spec_from_file_location("bench_job_sort_100b", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def uniform(rng):
    return rng.integers(0, 256, (ROWS, 10), dtype=np.uint8)


def tie_on(prefix):
    def keys(rng):
        key = uniform(rng)
        key[:, :prefix] = key[rng.integers(0, 16, ROWS), :prefix]
        return key
    return keys


def duplicates(rng):
    return uniform(rng)[rng.integers(0, 100, ROWS)]


SHAPES = {
    "uniform": uniform,
    "ties_on_four_bytes": tie_on(4),
    "ties_on_eight_bytes": tie_on(8),
    "duplicates": duplicates,
    "sorted": lambda rng: np.unique(uniform(rng), axis=0),
}


def the_table(job, shape):
    return job.table_of(SHAPES[shape](np.random.default_rng([32, len(shape)])))


def passes(checks):
    assert set(checks) == NUMBERS
    return all(value <= limit for value, limit in checks.values())


def test_the_job_file_takes_nothing_from_the_program(job):
    with open(job.__file__) as fh:
        source = fh.read()
    assert "dryad_tpu" not in source and "import jax" not in source
    assert (job.KEY_BYTES, job.PAYLOAD_BYTES, job.RECORD_BYTES) == (10, 90, 100)
    assert job.min_bytes({"rows": 2**23}) == 200 * 2**23  # the published record
    assert job.input_rows({"rows": 7}) == 7


def test_make_table_is_the_seeds_and_the_reference_passes(job):
    a = job.make_table(np.random.default_rng([5, 0]), PARAMS, None, 0)
    b = job.make_table(np.random.default_rng([5, 0]), PARAMS, None, 0)
    c = job.make_table(np.random.default_rng([5, 1]), PARAMS, None, 1)
    key, payload = a["arrays"]["key"], a["arrays"]["payload"]
    assert key.shape == (ROWS, 10) and payload.shape == (ROWS, 90)
    assert key.dtype == payload.dtype == np.uint8
    assert key.tobytes() == b["arrays"]["key"].tobytes() != c["arrays"]["key"].tobytes()
    # the reference's order is memcmp order: Python's own bytes compare
    assert list(map(bytes, a["want_key"])) == sorted(map(bytes, key))
    reference = {"key": a["want_key"], "payload": job.key_payload(a["want_key"])}
    checks = job.compare(a, reference, PARAMS)
    assert passes(checks) and all(v == 0 and lim == 0 for v, lim in checks.values())


def test_every_payload_byte_depends_on_the_key_and_its_position(job):
    key = uniform(np.random.default_rng(1))[:512]
    payload = job.key_payload(key)
    for byte in range(10):  # one key bit flipped moves every payload byte
        other = key.copy()
        other[:, byte] ^= 1
        changed = (job.key_payload(other) != payload).mean(axis=0)
        assert changed.min() > 0.9, (byte, changed.min())
    # no two payload words of a row are alike
    words = payload[:, :88].reshape(len(key), 11, 8)
    assert all(len({bytes(w) for w in row}) == 11 for row in words)


@pytest.mark.parametrize("shape", ["ties_on_four_bytes", "ties_on_eight_bytes"])
def test_the_control_fails_on_order_alone(job, shape):
    table = the_table(job, shape)
    checks = job.compare(table, job.control(table, PARAMS), PARAMS)
    assert not passes(checks)
    assert checks["sort100b.keys_out_of_order"][0] > 0
    assert checks["sort100b.rows_missing"][0] == 0
    assert checks["sort100b.payloads_off_key"][0] == 0  # its payloads are its keys'


def test_uniform_keys_at_this_size_never_tie_so_the_control_needs_the_cells_rows(job):
    table = the_table(job, "uniform")
    assert passes(job.compare(table, job.control(table, PARAMS), PARAMS))
    # at the cell's 2^23 rows some 2^13 pairs tie on the first four bytes
    assert 2**23 * (2**23 - 1) // 2 // 2**32 == 8191


@pytest.mark.parametrize("fault", ["word_swapped", "word_dropped", "row_dropped",
                                   "rows_swapped", "wrong_dtype"])
def test_a_broken_answer_fails(job, fault):
    table = the_table(job, "duplicates")
    key = table["want_key"].copy()
    payload = job.key_payload(key)
    if fault == "word_swapped":  # two payload words of one row, swapped
        payload[17, 8:12], payload[17, 12:16] = (payload[17, 12:16].copy(),
                                                  payload[17, 8:12].copy())
    elif fault == "word_dropped":
        payload[:, 88:] = 0
    elif fault == "row_dropped":
        key, payload = key[:-1], payload[:-1]
    elif fault == "rows_swapped":
        distinct = np.flatnonzero((key[1:] != key[:-1]).any(axis=1))[0]
        key[[distinct, distinct + 1]] = key[[distinct + 1, distinct]]
        payload = job.key_payload(key)
    elif fault == "wrong_dtype":
        payload = payload.astype(np.int32)
    checks = job.compare(table, {"key": key, "payload": payload}, PARAMS)
    assert any(value > limit for value, limit in checks.values()), checks
    if fault in ("word_swapped", "word_dropped"):
        assert checks["sort100b.payloads_off_key"][0] == (1 if fault == "word_swapped"
                                                          else ROWS)
        assert checks["sort100b.keys_out_of_order"][0] == 0
    if fault == "rows_swapped":
        assert checks["sort100b.keys_out_of_order"][0] == 2


@pytest.mark.parametrize("P", [1, 4])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_cells_query_is_exact(job, shape, P):
    table = the_table(job, shape)
    rows = len(table["want_key"])
    ctx = DryadContext(num_partitions_=P)
    query = job.bind(ctx, table, {"rows": rows})
    assert query.schema.field("key").ctype == BYTES(10)
    assert query.schema.field("payload").ctype == BYTES(90)
    for answer in (query.collect(), query.collect()):
        assert answer["key"].dtype == answer["payload"].dtype == np.uint8
        assert answer["key"].shape == (rows, 10)
        assert answer["payload"].shape == (rows, 90)
        assert answer["key"].tobytes() == table["want_key"].tobytes()
        checks = job.compare(table, answer, {"rows": rows})
        assert set(checks) == NUMBERS
        assert all(value == 0 and limit == 0 for value, limit in checks.values())
    events = ctx.events.events()
    spans = [e for e in events if e["kind"] == "span"]
    # the requery ingests nothing: one pack a BYTES column, in the fresh job
    assert [e["bytes"] for e in spans if e["name"] == "pack"] == [10 * rows, 90 * rows]
    assert [e["bytes"] for e in spans if e["name"] == "unpack"] == [
        10 * rows, 90 * rows] * 2
    dispatched = [e for e in spans if e.get("cat") == "execute"]
    assert len(dispatched) >= 2 and {e["row_words"] for e in dispatched} == {26}


def test_a_program_without_the_type_leaves_at_once(job):
    """The parent of the PR that added the cell raises TypeError on a
    2-D uint8 column; the job turns that into an exit, so the run ends
    with an error at once and not after a window of failed pairs."""
    class Parent:
        def from_arrays(self, arrays):
            raise TypeError("column 'key': unsupported dtype uint8")

    with pytest.raises(SystemExit, match="takes no .* uint8 column"):
        job.bind(Parent(), the_table(job, "uniform"), PARAMS)


def test_the_configuration_states_the_record_and_the_cut():
    with open(os.path.join(ROOT, "benchmarks", "configs", "gensort-100b-1c.json")) as fh:
        body = json.load(fh)
    assert (body["record_bytes"], body["key_bytes"], body["payload_bytes"]) == (100, 10, 90)
    assert body["reduced"] == ["rows"] and "rows" in body["reduced_why"]
    assert body["published_rows_a_chip"] == 39062500 == 10**10 // 256
    with open(os.path.join(ROOT, "benchmarks", "traffic", "sort_100b.json")) as fh:
        traffic = json.load(fh)
    assert traffic["rows"] == body["rows"] and traffic["rows"] in (2**23, 2**22)
    assert traffic["job"] == "sort_100b" and traffic["pool"] == 2
