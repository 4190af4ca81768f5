"""Checker self-tests: every graftlint rule must FIRE on a known-bad
mutation and stay SILENT on the clean fixture.

Each rule gets a minimal fixture project (built via
``Project.from_sources`` — the checkers read registries as AST
literals, so synthetic trees exercise the same code paths as the real
one) and a set of seeded mutations, each the original failure case the
legacy ``tests/test_*_lint.py`` suites guarded against (plus the new
determinism / host-transfer / recompile hazards).  A silently-broken
checker cannot pass CI: its mutation case stops firing.

Also here: the determinism-audit pin for the retry machinery
(``exec/failure.py``) — golden backoff values hardcoded so a change to
the seeding scheme (e.g. an accidental switch to process-salted
``hash()``) fails loudly.
"""

import pytest

from dryad_tpu.analysis.core import Project, run
from dryad_tpu.exec.failure import RetryPolicy


def _rules(sources, rule):
    report = run(Project.from_sources(sources), rules=[rule])
    return [f.rule for f in report.unsuppressed()]


def _assert_fires(sources, rule, n=None):
    fired = _rules(sources, rule)
    assert fired and set(fired) == {rule}, f"expected {rule}, got {fired}"
    if n is not None:
        assert len(fired) == n, f"expected {n} findings, got {len(fired)}"


def _mutate(sources, path, old, new):
    out = dict(sources)
    assert old in out[path], f"mutation anchor {old!r} missing in {path}"
    out[path] = out[path].replace(old, new)
    return out


# -- operand-registry --------------------------------------------------------

KERNELS = "dryad_tpu/exec/kernels.py"

KERNELS_CLEAN = '''\
import jax.numpy as jnp


def _k_string_code(ctx, p, cols):
    table = p["table"]
    ops = ctx.operand("table")
    return table.lookup(cols, operands=ops)


def _k_select(ctx, p, cols):
    return cols


def _k_do_while(ctx, p, cols):
    return cols


OPERAND_PARAMS = frozenset({("string_code", "table")})
_KERNELS = {
    "string_code": _k_string_code,
    "select": _k_select,
    "do_while": _k_do_while,
}


def build_stage_fn(stage):
    return None


def build_fused_fn(stages):
    return None
'''

FUSE = "dryad_tpu/plan/fuse.py"

FUSE_CLEAN = '''\
FUSABLE_OPS = frozenset({"select", "string_code"})
DRIVER_OPS = frozenset({"do_while"})
'''

OPERAND_FIXTURE = {KERNELS: KERNELS_CLEAN, FUSE: FUSE_CLEAN}


def test_operand_registry_clean_fixture():
    assert _rules(OPERAND_FIXTURE, "operand-registry") == []


@pytest.mark.parametrize(
    "old,new",
    [
        # bake the table into the trace
        (
            "return table.lookup(cols, operands=ops)",
            "baked = jnp.asarray(table)\n"
            "    return table.lookup(cols, operands=ops)",
        ),
        # table-method call without the operands routing
        (
            "return table.lookup(cols, operands=ops)",
            "return table.lookup(cols)",
        ),
        # ctx.operand() from a kernel with no registered param
        (
            "def _k_select(ctx, p, cols):\n    return cols",
            "def _k_select(ctx, p, cols):\n"
            "    ops = ctx.operand(\"x\")\n    return cols",
        ),
        # stale registry entry: the param is never used
        (
            'table = p["table"]\n'
            '    ops = ctx.operand("table")\n'
            "    return table.lookup(cols, operands=ops)",
            "return cols",
        ),
    ],
    ids=["bake", "no-operands-kw", "unregistered-ctx-operand", "stale"],
)
def test_operand_registry_fires(old, new):
    _assert_fires(
        _mutate(OPERAND_FIXTURE, KERNELS, old, new), "operand-registry"
    )


# -- fuse-classification -----------------------------------------------------


def test_fuse_classification_clean_fixture():
    assert _rules(OPERAND_FIXTURE, "fuse-classification") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        (FUSE, '"select", "string_code"', '"select", "string_code", "ghost"'),
        (
            KERNELS,
            '"do_while": _k_do_while,',
            '"do_while": _k_do_while,\n    "orphan": _k_select,',
        ),
        (FUSE, 'DRIVER_OPS = frozenset({"do_while"})',
         'DRIVER_OPS = frozenset({"do_while", "select"})'),
    ],
    ids=["unkernelled-admit", "unclassified-kernel", "overlap"],
)
def test_fuse_classification_fires(path, old, new):
    _assert_fires(
        _mutate(OPERAND_FIXTURE, path, old, new), "fuse-classification"
    )


# -- host-transfer -----------------------------------------------------------

OOC = "dryad_tpu/exec/outofcore.py"
STRINGCODE = "dryad_tpu/ops/stringcode.py"

HOST_FIXTURE = {
    KERNELS: KERNELS_CLEAN,
    FUSE: FUSE_CLEAN,
    OOC: '''\
def _group_partial_tree(self, node):
    def merge_local(batches):
        return batches[0]
    return merge_local
''',
    STRINGCODE: '''\
import numpy as np


def palette_domain(n):
    return max(4, n)


class CodeTable:
    operand_arity = 3

    def build(self, pairs):
        return np.asarray(pairs)

    def lookup(self, h0, h1, operands=None):
        return h0
''',
}


def test_host_transfer_clean_fixture():
    # note build()'s np.asarray is FINE: host-side builder, no operands=
    assert _rules(HOST_FIXTURE, "host-transfer") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        (KERNELS, "def _k_select(ctx, p, cols):\n    return cols",
         "def _k_select(ctx, p, cols):\n    return cols.item()"),
        (KERNELS, "def _k_select(ctx, p, cols):\n    return cols",
         "def _k_select(ctx, p, cols):\n    return float(jnp.sum(cols))"),
        (FUSE, "DRIVER_OPS = frozenset",
         "def plan(x):\n    import jax\n    return jax.device_get(x)\n\n\n"
         "DRIVER_OPS = frozenset"),
        (OOC, "return batches[0]",
         "import numpy as np\n        return np.asarray(batches[0])"),
        (STRINGCODE, "def lookup(self, h0, h1, operands=None):\n        return h0",
         "def lookup(self, h0, h1, operands=None):\n"
         "        return np.asarray(h0)"),
    ],
    ids=["kernel-item", "kernel-float-traced", "fuse-device-get",
         "merge-closure", "traced-table-method"],
)
def test_host_transfer_fires(path, old, new):
    _assert_fires(_mutate(HOST_FIXTURE, path, old, new), "host-transfer")


def test_host_transfer_lost_anchor_is_a_finding():
    mutated = _mutate(
        HOST_FIXTURE, OOC, "def merge_local", "def merge_other"
    )
    _assert_fires(mutated, "host-transfer")


# -- layer-imports / placement-snapshot --------------------------------------

CT = "dryad_tpu/exec/combinetree.py"

CT_CLEAN = '''\
def _cosine(a, b):
    return sum(a[k] * b.get(k, 0.0) for k in sorted(a))


def place(snapshot, centroids):
    return 0


def plan_groups(snapshots, k):
    return [list(snapshots)]


class CombineTreePlanner:
    def plan(self, snapshots):
        return plan_groups(snapshots, 2)
'''

LAYER_FIXTURE = {
    CT: CT_CLEAN,
    "dryad_tpu/redundancy/coded.py": (
        "from dryad_tpu.exec import partial\n"
    ),
}


def test_layer_imports_clean_fixture():
    assert _rules(LAYER_FIXTURE, "layer-imports") == []


@pytest.mark.parametrize(
    "path,new_header",
    [
        (CT, "from dryad_tpu.cluster import scheduler\n"),
        ("dryad_tpu/redundancy/coded.py",
         "import dryad_tpu.exec.outofcore\n"),
        ("dryad_tpu/redundancy/coded.py",
         "from dryad_tpu.cluster.localjob import Gang\n"),
    ],
    ids=["combinetree-cluster", "redundancy-outofcore",
         "redundancy-cluster"],
)
def test_layer_imports_fires(path, new_header):
    mutated = dict(LAYER_FIXTURE)
    mutated[path] = new_header + mutated[path]
    _assert_fires(mutated, "layer-imports")


def test_placement_snapshot_clean_fixture():
    assert _rules(LAYER_FIXTURE, "placement-snapshot") == []


@pytest.mark.parametrize(
    "old,new",
    [
        ("def place(snapshot, centroids):\n    return 0",
         "def place(snapshot, centroids):\n    return snapshot.data"),
        ("return plan_groups(snapshots, 2)",
         "return [s.to_numpy() for s in snapshots]"),
        # structural drift: a scanned surface disappears entirely
        ("def _cosine(a, b):", "def _cosine_renamed(a, b):"),
    ],
    ids=["place-reads-data", "planner-reads-payload", "lost-anchor"],
)
def test_placement_snapshot_fires(old, new):
    _assert_fires(_mutate(LAYER_FIXTURE, CT, old, new),
                  "placement-snapshot")


# -- coded-linearity ---------------------------------------------------------

DEC = "dryad_tpu/redundancy/decs.py"

LINEARITY_FIXTURE = {
    DEC: '''\
from dryad_tpu.api.decomposable import Decomposable

SUM = Decomposable(linear=True, identity=0)
COUNT = Decomposable(linear=False)
''',
}


def test_coded_linearity_clean_fixture():
    assert _rules(LINEARITY_FIXTURE, "coded-linearity") == []


def test_coded_linearity_fires_without_identity():
    _assert_fires(
        _mutate(LINEARITY_FIXTURE, DEC,
                "Decomposable(linear=True, identity=0)",
                "Decomposable(linear=True)"),
        "coded-linearity",
    )


def test_coded_linearity_exempts_pytest_raises_blocks():
    sources = {
        "tests/test_neg.py": '''\
import pytest

from dryad_tpu.api.decomposable import Decomposable


def test_rejects_linear_without_identity():
    with pytest.raises(ValueError):
        Decomposable(linear=True)
''',
    }
    assert _rules(sources, "coded-linearity") == []


# -- event-schema ------------------------------------------------------------

EVENTS = "dryad_tpu/exec/events.py"
EMITTER = "dryad_tpu/obs/emitter.py"

EVENT_FIXTURE = {
    EVENTS: '''\
EVENT_KINDS = {"tick": "one tick; n"}
EVENT_PAYLOADS = {"tick": (("n",), ("extra",))}
''',
    EMITTER: '''\
def go(log):
    log.emit("tick", n=1)
    log.emit("tick", n=2, extra="y")
''',
}


def test_event_schema_clean_fixture():
    assert _rules(EVENT_FIXTURE, "event-schema") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        (EMITTER, 'log.emit("tick", n=1)', 'log.emit("boom", n=1)'),
        (EMITTER, 'log.emit("tick", n=1)', 'log.emit("tick")'),
        (EMITTER, 'log.emit("tick", n=1)', 'log.emit("tick", n=1, w=2)'),
        (EVENTS, '{"tick": "one tick; n"}',
         '{"tick": "one tick; n", "ghost": "never emitted"}'),
        (EVENTS, 'EVENT_PAYLOADS = {"tick": (("n",), ("extra",))}',
         'EVENT_PAYLOADS = {}'),
        (EVENTS, '"one tick; n"', '""'),
    ],
    ids=["undocumented-kind", "missing-required-key", "key-off-spec",
         "stale-kind", "payload-table-gap", "empty-doc"],
)
def test_event_schema_fires(path, old, new):
    mutated = _mutate(EVENT_FIXTURE, path, old, new)
    fired = _rules(mutated, "event-schema")
    assert fired and set(fired) == {"event-schema"}, fired


def test_event_schema_star_kwargs_checked_for_inclusion_only():
    # forwarding sites can't prove required keys statically; they must
    # not false-positive, but explicit off-spec keys still flag
    ok = _mutate(EVENT_FIXTURE, EMITTER, "def go(log):",
                 "def fwd(log, blob):\n"
                 '    log.emit("tick", **blob)\n\n\n'
                 "def go(log):")
    assert _rules(ok, "event-schema") == []
    bad = _mutate(EVENT_FIXTURE, EMITTER, "def go(log):",
                  "def fwd(log, blob):\n"
                  '    log.emit("tick", w=1, **blob)\n\n\n'
                  "def go(log):")
    _assert_fires(bad, "event-schema", n=1)


# -- metric-key --------------------------------------------------------------

TELEMETRY = "dryad_tpu/obs/telemetry.py"
METRIC_EMITTER = "dryad_tpu/serve/metricsrc.py"

METRIC_FIXTURE = {
    TELEMETRY: '''\
METRIC_KEYS = {
    "ticks": "tick counter",
    "depth": "queue depth gauge",
    "lat_s": "latency histogram",
}
''',
    METRIC_EMITTER: '''\
def go(store):
    store.incr("ticks", tenant="a")
    store.set_gauge("depth", 3)
    store.observe_latency("lat_s", 0.25, tenant="a")
''',
}


def test_metric_key_clean_fixture():
    assert _rules(METRIC_FIXTURE, "metric-key") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        (METRIC_EMITTER, 'store.incr("ticks", tenant="a")',
         'store.incr("boom", tenant="a")'),
        (TELEMETRY, '"ticks": "tick counter",',
         '"ticks": "tick counter",\n    "ghost": "never emitted",'),
        (TELEMETRY, '"tick counter"', '""'),
        (METRIC_EMITTER, 'store.set_gauge("depth", 3)',
         'name = "depth"\n    store.set_gauge(name, 3)'),
        (TELEMETRY, "METRIC_KEYS", "OTHER_KEYS"),
    ],
    ids=["unregistered-metric", "stale-registry-key", "empty-doc",
         "non-literal-name", "missing-registry"],
)
def test_metric_key_fires(path, old, new):
    mutated = _mutate(METRIC_FIXTURE, path, old, new)
    fired = _rules(mutated, "metric-key")
    assert fired and set(fired) == {"metric-key"}, fired


def test_metric_key_unregistered_and_stale_both_fire():
    # renaming an emit site is BOTH an unregistered emission and a
    # stale registry entry — the rule reports each direction
    mutated = _mutate(
        METRIC_FIXTURE, METRIC_EMITTER,
        'store.incr("ticks", tenant="a")',
        'store.incr("tocks", tenant="a")',
    )
    _assert_fires(mutated, "metric-key", n=2)


# -- kernel-determinism ------------------------------------------------------

DET = "dryad_tpu/ops/det.py"

DET_CLEAN = '''\
import random


def f(seed, xs):
    rng = random.Random(seed)
    seen = {}
    for x in sorted(xs):
        if id(x) in seen:
            continue
        seen[id(x)] = x
    return rng.random()
'''


def test_kernel_determinism_clean_fixture():
    # seeded Random, id()-as-key, sorted iteration: all legal idioms
    assert _rules({DET: DET_CLEAN}, "kernel-determinism") == []


@pytest.mark.parametrize(
    "old,new",
    [
        ("import random", "import random\nimport time"),
        ("rng = random.Random(seed)",
         "rng = random.Random(seed)\n    t = time.time()"),
        ("rng = random.Random(seed)", "rng = random.Random()"),
        ("return rng.random()", "return random.random()"),
        ("return rng.random()",
         "import numpy as np\n    return np.random.rand(3)"),
        ("return rng.random()",
         "import os\n    return os.environ[\"X\"]"),
        ("return rng.random()",
         "import os\n    return os.getenv(\"X\")"),
        ("return rng.random()",
         "from time import perf_counter\n    return perf_counter()"),
        ("seen[id(x)] = x", "seen[x] = id(x)"),
        ("for x in sorted(xs):", "for x in {1, 2, 3}:"),
        ("return rng.random()", "return [k for k in {1, 2}]"),
        ("rng = random.Random(seed)",
         "global _STATE\n    rng = random.Random(seed)"),
    ],
    ids=["unused-import-ok-anchor", "wall-clock", "unseeded-Random",
         "module-random", "np-random", "os-environ", "os-getenv",
         "from-time-import", "id-as-value", "set-iteration",
         "set-comprehension", "global-stmt"],
)
def test_kernel_determinism_fires(old, new):
    sources = _mutate({DET: DET_CLEAN}, DET, old, new)
    if "import time" in new and "time.time" not in new:
        # the import alone is not a hazard; pair it with the clock read
        sources = _mutate(sources, DET, "return rng.random()",
                          "return time.time()")
    _assert_fires(sources, "kernel-determinism")


def test_kernel_determinism_flags_module_mutable_writes():
    body = '''\
CACHE = {}


def f(k, v):
    CACHE[k] = v
    CACHE.update({k: v})
    return CACHE
'''
    _assert_fires({DET: body}, "kernel-determinism", n=2)


def test_kernel_determinism_allows_seeded_np_rng():
    body = "import numpy as np\n\n\ndef f(s):\n    return np.random.default_rng(s)\n"
    assert _rules({DET: body}, "kernel-determinism") == []
    bad = body.replace("default_rng(s)", "default_rng()")
    _assert_fires({DET: bad}, "kernel-determinism", n=1)


def test_kernel_determinism_ignores_files_outside_scope():
    # the executor layer legitimately reads clocks; scope excludes it
    body = "import time\n\n\ndef f():\n    return time.time()\n"
    assert _rules({"dryad_tpu/exec/executor.py": body},
                  "kernel-determinism") == []


# -- recompile-hazard --------------------------------------------------------

TBL = "dryad_tpu/ops/table.py"

TBL_CLEAN = '''\
import numpy as np

from dryad_tpu.ops.stringcode import palette_domain


class Table:
    operand_arity = 2

    def __init__(self, pairs):
        K = len(pairs)
        S = 2 * palette_domain(K)
        self.cap = S
        self.codes = np.zeros(S, np.uint32)

    def rebuild(self):
        self.codes = np.zeros(self.cap, np.uint32)

    def operand_signature(self):
        return (self.codes.shape,)
'''


def test_recompile_hazard_clean_fixture():
    assert _rules({TBL: TBL_CLEAN}, "recompile-hazard") == []


@pytest.mark.parametrize(
    "old,new",
    [
        ("np.zeros(S, np.uint32)", "np.zeros(K, np.uint32)"),
        ("np.zeros(S, np.uint32)", "np.zeros(len(pairs), np.uint32)"),
        # raw len() stored on self leaks into ANOTHER method's shape
        ("self.cap = S", "self.cap = K"),
    ],
    ids=["raw-name-dim", "direct-len-dim", "raw-attr-dim"],
)
def test_recompile_hazard_fires_in_operand_class(old, new):
    _assert_fires(_mutate({TBL: TBL_CLEAN}, TBL, old, new),
                  "recompile-hazard")


def test_recompile_hazard_ignores_classes_without_operand_surface():
    body = TBL_CLEAN.replace("operand_arity = 2\n\n    ", "").replace(
        "np.zeros(S, np.uint32)", "np.zeros(len(pairs), np.uint32)"
    ).replace(
        "def operand_signature(self):\n        return (self.codes.shape,)",
        "def shape(self):\n        return self.codes.shape",
    )
    assert _rules({TBL: body}, "recompile-hazard") == []


def test_recompile_hazard_traced_bodies():
    assert _rules(OPERAND_FIXTURE, "recompile-hazard") == []
    cases = {
        "len-dim": ("def _k_select(ctx, p, cols):\n    return cols",
                    "def _k_select(ctx, p, cols):\n"
                    "    return jnp.zeros((len(cols), 4))"),
        "host-numpy": ("def _k_select(ctx, p, cols):\n    return cols",
                       "def _k_select(ctx, p, cols):\n"
                       "    import numpy as np\n    return np.zeros(4)"),
        "off-palette-literal": (
            "def _k_select(ctx, p, cols):\n    return cols",
            "def _k_select(ctx, p, cols):\n    return jnp.zeros((24,))"),
    }
    for name, (old, new) in cases.items():
        fired = _rules(_mutate(OPERAND_FIXTURE, KERNELS, old, new),
                       "recompile-hazard")
        assert fired == ["recompile-hazard"], (name, fired)
    # pow2 and sub-16 literal dims ride the palette fine
    ok = _mutate(OPERAND_FIXTURE, KERNELS,
                 "def _k_select(ctx, p, cols):\n    return cols",
                 "def _k_select(ctx, p, cols):\n"
                 "    return jnp.zeros((32, 4))")
    assert _rules(ok, "recompile-hazard") == []


# -- sync-in-dispatch-loop ---------------------------------------------------

PIPE = "dryad_tpu/exec/pipeline.py"

PIPE_CLEAN = '''\
class DispatchWindow:
    def submit(self, tag, fetch):
        self.pending.append((tag, fetch))

    def _collect(self):
        tag, fetch = self.pending.pop(0)
        value = fetch()
        self.done.append((tag, value))

    def drain(self):
        return list(self.done)
'''

DISPATCH_HELPER = "dryad_tpu/exec/hostutil.py"

DISPATCH_FIXTURE = {
    PIPE: PIPE_CLEAN,
    # np.asarray OUTSIDE a dispatch class is ordinary host-side code
    DISPATCH_HELPER: '''\
import numpy as np


def to_host(x):
    return np.asarray(x)
''',
}


def test_sync_in_dispatch_loop_clean_fixture():
    # fetch() at the collector is the sanctioned blocking point, and
    # the helper module's np.asarray lives outside any dispatch class
    assert _rules(DISPATCH_FIXTURE, "sync-in-dispatch-loop") == []


@pytest.mark.parametrize(
    "old,new",
    [
        # the literal re-serializer on the collector thread
        ("value = fetch()",
         "value = fetch()\n        value.block_until_ready()"),
        # inline D2H inside the collect loop
        ("value = fetch()", "value = jax.device_get(fetch())"),
        # scalar readback while draining
        ("return list(self.done)",
         "return [v.item() for _t, v in self.done]"),
        # the sneaky blocking copy on the submit path
        ("self.pending.append((tag, fetch))",
         "self.pending.append((tag, np.asarray(fetch)))"),
    ],
    ids=["block-until-ready", "device-get", "item", "np-asarray"],
)
def test_sync_in_dispatch_loop_fires(old, new):
    _assert_fires(
        _mutate(DISPATCH_FIXTURE, PIPE, old, new),
        "sync-in-dispatch-loop", n=1,
    )


def test_sync_in_dispatch_loop_exempts_traced_asarray():
    # jnp.asarray is a trace op: device-side, non-blocking, legal
    ok = _mutate(DISPATCH_FIXTURE, PIPE, "value = fetch()",
                 "value = jnp.asarray(fetch())")
    assert _rules(ok, "sync-in-dispatch-loop") == []


def test_sync_in_dispatch_loop_lost_anchor_is_a_finding():
    # pipeline.py without a DispatchWindow class = structural drift
    mutated = _mutate(
        DISPATCH_FIXTURE, PIPE, "class DispatchWindow:", "class Window:"
    )
    _assert_fires(mutated, "sync-in-dispatch-loop", n=1)


# -- determinism audit pin (exec/failure.py) ---------------------------------


def test_retry_backoff_golden_values_are_process_stable():
    """The retry schedule must be a pure function of (seed, key,
    failures) — seeded via str -> sha512, NOT the per-process-salted
    hash().  Golden values pin the cross-process contract: if this
    fails, every chaos replay and differential fault test is drifting.
    """
    p = RetryPolicy(seed=7)
    assert [round(p.backoff("stage:3", n), 12) for n in (1, 2, 3)] == [
        0.070147149864, 0.109707150614, 0.266841169515,
    ]
    # distinct seeds and keys de-correlate the jitter
    assert round(RetryPolicy(seed=8).backoff("stage:3", 1), 12) == \
        0.073392437727
    assert round(p.backoff("stage:4", 1), 12) == 0.065387691467
    # and the schedule is reproducible within a process too
    assert p.backoff("stage:3", 1) == p.backoff("stage:3", 1)


# -- span-discipline ---------------------------------------------------------

SPANPY = "dryad_tpu/obs/span.py"
STREAM = "dryad_tpu/exec/stream.py"

SPAN_FIXTURE = {
    SPANPY: '''\
class Span:
    pass


class Tracer:
    def span(self, name, **kw):
        return Span()
''',
    STREAM: '''\
from dryad_tpu.obs.span import Tracer

tracer = Tracer()


def run_stage(chunks):
    with tracer.span("execute", cat="execute"):
        for c in chunks:
            with tracer.span("chunk", cat="stream") as sp:
                pass
''',
}


def test_span_discipline_clean_fixture():
    assert _rules(SPAN_FIXTURE, "span-discipline") == []


@pytest.mark.parametrize(
    "old,new",
    [
        # span held as a value: never closes on the exception path
        (
            'with tracer.span("execute", cat="execute"):',
            'sp = tracer.span("execute", cat="execute")\n'
            "    if True:",
        ),
        # span opened inside an expression, not a with-item
        (
            'with tracer.span("chunk", cat="stream") as sp:',
            'sp = enter(tracer.span("chunk", cat="stream"))\n'
            "            if True:",
        ),
        # direct Span construction bypasses the tracer factory
        (
            "for c in chunks:",
            "bare = Span()\n    for c in chunks:",
        ),
    ],
)
def test_span_discipline_fires(old, new):
    _assert_fires(_mutate(SPAN_FIXTURE, STREAM, old, new),
                  "span-discipline", n=1)


def test_span_discipline_exempts_span_py_itself():
    # the factory file returns Spans by design
    assert _rules(
        {SPANPY: SPAN_FIXTURE[SPANPY]}, "span-discipline"
    ) == []


# -- config-key --------------------------------------------------------------

CONFIGPY = "dryad_tpu/utils/config.py"
USER = "dryad_tpu/exec/driver.py"

CONFIG_FIXTURE = {
    CONFIGPY: '''\
class DryadConfig:
    chunk_rows: int = 4096
    straggler_floor_ratio: float = 1.5

    def validate(self):
        pass


CONFIG_KEYS = {
    "chunk_rows": "rows per streamed chunk",
    "straggler_floor_ratio": "spare-launch floor multiplier",
}
''',
    USER: '''\
def run(ctx, cfg):
    ctx.config.validate()
    n = ctx.config.chunk_rows
    ratio = cfg.straggler_floor_ratio
    return getattr(ctx.config, "chunk_rows", n) * ratio


def tune(runtime):
    import jax

    jax.config.update("jax_enable_x64", True)
''',
}


def test_config_key_clean_fixture():
    assert _rules(CONFIG_FIXTURE, "config-key") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        # typo'd attribute read (the bug the rule exists for)
        (USER, "ctx.config.chunk_rows", "ctx.config.chunks_rows"),
        # typo'd getattr key: silently returns the default forever
        (
            USER,
            'getattr(ctx.config, "chunk_rows", n)',
            'getattr(ctx.config, "chunk_row", n)',
        ),
        # field added to the dataclass but not documented
        (
            CONFIGPY,
            "chunk_rows: int = 4096",
            "chunk_rows: int = 4096\n    new_knob: int = 1",
        ),
        # stale schema entry: key documented, field deleted
        (
            CONFIGPY,
            "    straggler_floor_ratio: float = 1.5\n",
            "",
        ),
        # doc must be a non-empty one-liner
        (
            CONFIGPY,
            '"rows per streamed chunk"',
            '""',
        ),
        # a documented field that nothing outside config.py reads
        (
            USER,
            "    ratio = cfg.straggler_floor_ratio\n",
            "    ratio = 1.5\n",
        ),
    ],
)
def test_config_key_fires(path, old, new):
    _assert_fires(_mutate(CONFIG_FIXTURE, path, old, new), "config-key")


def test_config_key_ignores_jax_config():
    # jax.config.update is a different animal — never checked
    assert "jax.config.update" in CONFIG_FIXTURE[USER]
    assert _rules(CONFIG_FIXTURE, "config-key") == []


# -- collective-order --------------------------------------------------------

SHUFFLEPY = "dryad_tpu/ops/shuffle.py"

COLLECTIVE_FIXTURE = {
    SHUFFLEPY: '''\
import jax


def exchange(send, send_valid, overflow, axis_name):
    recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=True)
    recv_valid = jax.lax.all_to_all(send_valid, axis_name, 0, 0, tiled=True)
    overflow = jax.lax.psum(overflow, axis_name) > 0
    return recv, recv_valid, overflow


def exchange_staged(blocks, overflow, axis_name, schedule):
    for perm in schedule:
        blocks = [jax.lax.ppermute(b, axis_name, perm) for b in blocks]
    overflow = jax.lax.psum(overflow, axis_name) > 0
    return blocks, overflow


def rank_column(local, axes):
    counts = jax.lax.all_gather(local, axes)
    total = jax.lax.psum(local, axes)
    return counts, total


def build_stage_fn(stage, axes):
    def fn(inputs, replicated):
        overflow = jax.lax.psum(stage.overflow, axes) > 0
        return inputs, overflow

    return fn
''',
}


def test_collective_order_clean_fixture():
    assert _rules(COLLECTIVE_FIXTURE, "collective-order") == []


@pytest.mark.parametrize(
    "old,new,n",
    [
        # flag reduction hoisted ahead of the data all_to_alls: two
        # fused members disagreeing on this order is the TPU deadlock
        # case (both later all_to_alls now trail the psum -> 2 findings)
        (
            "    recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=True)\n",
            "    recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=True)\n"
            "    early = jax.lax.psum(overflow, axis_name)\n"
            "    recv2 = jax.lax.all_to_all(recv, axis_name, 0, 0, tiled=True)\n",
            2,
        ),
        # a ppermute issued after the staged loop's psum
        (
            "    overflow = jax.lax.psum(overflow, axis_name) > 0\n"
            "    return blocks, overflow",
            "    overflow = jax.lax.psum(overflow, axis_name) > 0\n"
            "    blocks = [jax.lax.ppermute(b, axis_name, None) for b in blocks]\n"
            "    return blocks, overflow",
            1,
        ),
        # gather after reduction inside one body
        (
            "    total = jax.lax.psum(local, axes)\n",
            "    total = jax.lax.psum(local, axes)\n"
            "    extra = jax.lax.all_gather(total, axes)\n",
            1,
        ),
    ],
)
def test_collective_order_fires(old, new, n):
    _assert_fires(
        _mutate(COLLECTIVE_FIXTURE, SHUFFLEPY, old, new),
        "collective-order", n=n,
    )


def test_collective_order_scopes_are_independent():
    # the module mixes psum-last bodies with a nested fn issuing its own
    # psum; nesting must never cross-contaminate the outer sequence
    src = _mutate(
        COLLECTIVE_FIXTURE, SHUFFLEPY,
        "def build_stage_fn(stage, axes):",
        '''\
def outer_then_inner(x, axes):
    x = jax.lax.psum(x, axes)

    def inner(y):
        return jax.lax.ppermute(y, axes, None)

    return inner(x)


def build_stage_fn(stage, axes):''',
    )
    assert _rules(src, "collective-order") == []


# -- serve-layering ----------------------------------------------------------

PIPELINE = "dryad_tpu/exec/pipeline.py"
SERVICE = "dryad_tpu/serve/service.py"

PIPELINE_CLEAN = '''\
import threading


class DispatchWindow:
    def __init__(self, depth):
        self.depth = depth

    def submit(self, tag, fetch):
        pass
'''

SERVICE_CLEAN = '''\
from dryad_tpu.api.context import DryadContext
from dryad_tpu.exec.pipeline import DispatchWindow
from dryad_tpu.utils.logging import get_logger


class QueryService:
    def __init__(self, ctx):
        self.ctx = ctx
        self.window = DispatchWindow(depth=ctx.config.dispatch_depth)
'''

SERVE_FIXTURE = {PIPELINE: PIPELINE_CLEAN, SERVICE: SERVICE_CLEAN}


def test_serve_layering_clean_fixture():
    assert _rules(SERVE_FIXTURE, "serve-layering") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        # the engine growing a dependency on the service inverts the
        # whole tier: the window must never know tenants exist
        (
            PIPELINE,
            "import threading",
            "import threading\nfrom dryad_tpu.serve.service import QueryService",
        ),
        # direct jax from serve/ bypasses the driver-thread ownership
        # the api/exec entry points enforce
        (
            SERVICE,
            "from dryad_tpu.api.context import DryadContext",
            "import jax\nfrom dryad_tpu.api.context import DryadContext",
        ),
        # reaching into the planner skips the public surface
        (
            SERVICE,
            "from dryad_tpu.exec.pipeline import DispatchWindow",
            "from dryad_tpu.plan.lower import lower",
        ),
        # anchor drift: the scan must notice QueryService moving away
        (
            SERVICE,
            "class QueryService:",
            "class QuerySvc:",
        ),
    ],
    ids=["engine-imports-serve", "serve-imports-jax",
         "serve-imports-plan", "anchor-drift"],
)
def test_serve_layering_fires(path, old, new):
    _assert_fires(
        _mutate(SERVE_FIXTURE, path, old, new), "serve-layering"
    )


# -- rewrite-layering --------------------------------------------------------

OUTOFCORE = "dryad_tpu/exec/outofcore.py"
CONTROLLER = "dryad_tpu/rewrite/controller.py"

OUTOFCORE_CLEAN = '''\
import numpy as np


class StreamExecutor:
    def __init__(self, ctx):
        self.rewriter = getattr(ctx, "rewriter", None)
'''

CONTROLLER_CLEAN = '''\
import threading

from dryad_tpu.exec.events import EVENT_KINDS
from dryad_tpu.obs.diagnose import DiagnosisEngine
from dryad_tpu.rewrite.actions import RewriteAction


class RewriteController:
    def __init__(self, config=None, events=None):
        self.events = events
        self._lock = threading.Lock()
'''

REWRITE_FIXTURE = {OUTOFCORE: OUTOFCORE_CLEAN, CONTROLLER: CONTROLLER_CLEAN}


def test_rewrite_layering_clean_fixture():
    assert _rules(REWRITE_FIXTURE, "rewrite-layering") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        # the engine importing the policy layer inverts the contract:
        # drivers hold the controller by handle only
        (
            OUTOFCORE,
            "import numpy as np",
            "import numpy as np\n"
            "from dryad_tpu.rewrite.controller import RewriteController",
        ),
        # direct jax makes the policy fold a device client
        (
            CONTROLLER,
            "import threading",
            "import threading\n\nimport jax",
        ),
        # reaching into worker control (cluster/) from policy code
        (
            CONTROLLER,
            "from dryad_tpu.obs.diagnose import DiagnosisEngine",
            "from dryad_tpu.cluster.localjob import LocalJobSubmission",
        ),
        # exec machinery beyond the schema registry is off limits
        (
            CONTROLLER,
            "from dryad_tpu.exec.events import EVENT_KINDS",
            "from dryad_tpu.exec.executor import GraphExecutor",
        ),
        # anchor drift: the scan must notice the controller moving
        (
            CONTROLLER,
            "class RewriteController:",
            "class ReplanController:",
        ),
    ],
    ids=["engine-imports-rewrite", "rewrite-imports-jax",
         "rewrite-imports-cluster", "rewrite-imports-exec-machinery",
         "anchor-drift"],
)
def test_rewrite_layering_fires(path, old, new):
    _assert_fires(
        _mutate(REWRITE_FIXTURE, path, old, new), "rewrite-layering"
    )


# -- mailbox-discipline ------------------------------------------------------

GANGWIN = "dryad_tpu/cluster/gangwindow.py"

GANGWIN_CLEAN = '''\
class GangDispatchWindow:
    def __init__(self, depth):
        self.depth = depth

    def submit(self, tag, drain):
        pass

    def ready(self):
        return ()

    def drain(self):
        return ()

    def close(self, workers=None):
        pass
'''

GANGLJ = "dryad_tpu/cluster/localjob.py"

GANGLJ_CLEAN = '''\
from dryad_tpu.cluster.gangwindow import GangDispatchWindow


class Submission:
    def _command_round_trip(self, i, cmd):
        return {}

    def submit_windowed(self, chunks, depth):
        win = GangDispatchWindow(depth)
        results = {}
        try:
            for k, chunk in enumerate(chunks):
                for i in range(2):
                    self._post(i, chunk)

                def drain(chunk=chunk):
                    # the sanctioned blocking half: the closure is run
                    # by the collector, so waits in here are the job
                    for p in self._procs:
                        p.wait(1.0)
                    return chunk

                win.submit(k, drain)
                for tag, value, err in win.ready():
                    results[tag] = value
            for tag, value, err in win.drain():
                results[tag] = value
        finally:
            win.close(workers=2)
        return results

    def submit_serial(self, cmds):
        # no window in sight: synchronous round trips are fine here
        out = []
        for cmd in cmds:
            out.append(self._command_round_trip(0, cmd))
        return out

    def shutdown(self):
        # waits in a loop that never submits are also fine
        for p in self._procs:
            p.wait(5.0)

    def _post(self, i, chunk):
        pass
'''

MAILBOX_FIXTURE = {GANGWIN: GANGWIN_CLEAN, GANGLJ: GANGLJ_CLEAN}


def test_mailbox_discipline_clean_fixture():
    # the drain closure's p.wait(), submit_serial's round trips, and
    # shutdown's wait loop must all stay exempt
    assert _rules(MAILBOX_FIXTURE, "mailbox-discipline") == []


@pytest.mark.parametrize(
    "old,new",
    [
        # a synchronous mailbox round trip re-serializes the window
        (
            "win.submit(k, drain)",
            "win.submit(k, drain)\n"
            "                st = self._command_round_trip(0, chunk)",
        ),
        # a process wait in the feed path can deadlock: the status it
        # waits on may only arrive after an envelope it has not posted
        (
            "self._post(i, chunk)",
            "self._post(i, chunk)\n"
            "                    self._procs[i].wait(5.0)",
        ),
        # the blocking drain belongs AFTER the feed loop
        (
            "win.submit(k, drain)",
            "win.submit(k, drain)\n"
            "                for tag, value, err in win.drain():\n"
            "                    results[tag] = value",
        ),
        # bare-name round trip helpers count too
        (
            "win.submit(k, drain)",
            "win.submit(k, drain)\n"
            "                _placed_round_trip(0, chunk)",
        ),
    ],
    ids=["round-trip-in-feed", "wait-in-feed", "drain-in-feed",
         "bare-round-trip"],
)
def test_mailbox_discipline_fires(old, new):
    _assert_fires(
        _mutate(MAILBOX_FIXTURE, GANGLJ, old, new), "mailbox-discipline"
    )


def test_mailbox_discipline_exempts_drain_closure_blocking():
    # even a round trip is fine INSIDE the nested drain closure — the
    # collector runs it, not the feed thread
    mutated = _mutate(
        MAILBOX_FIXTURE,
        GANGLJ,
        "for p in self._procs:\n"
        "                        p.wait(1.0)",
        "for p in self._procs:\n"
        "                        p.wait(1.0)\n"
        "                    self._command_round_trip(0, chunk)",
    )
    assert _rules(mutated, "mailbox-discipline") == []


def test_mailbox_discipline_lost_anchor_is_a_finding():
    mutated = _mutate(
        MAILBOX_FIXTURE, GANGWIN,
        "class GangDispatchWindow", "class GangCommandWindow",
    )
    _assert_fires(mutated, "mailbox-discipline")


# -- trace-context -----------------------------------------------------------

TRC_EVENTS = "dryad_tpu/exec/events.py"
TRC_EMITTER = "dryad_tpu/obs/emitter.py"

TRACE_FIXTURE = {
    TRC_EVENTS: '''\
EVENT_KINDS = {"span": "a span; qid", "tick": "one tick; n"}
EVENT_PAYLOADS = {
    "span": (("name",), ("qid",)),
    "tick": (("n",), ()),
}
QUERY_SCOPED_KINDS = ("span",)
''',
    TRC_EMITTER: '''\
def go(log, qid):
    log.emit("span", name="s", qid=qid)
    log.emit("tick", n=1)
''',
}


def test_trace_context_clean_fixture():
    assert _rules(TRACE_FIXTURE, "trace-context") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        # the original failure: one emit site forgets the stamp and
        # that event class drops out of every per-query fold
        (TRC_EMITTER, 'log.emit("span", name="s", qid=qid)',
         'log.emit("span", name="s")'),
        # a **blob forward does NOT satisfy the contract — the stamp
        # must be visible at the site
        (TRC_EMITTER, 'log.emit("span", name="s", qid=qid)',
         'log.emit("span", name="s", **{"qid": qid})'),
        # registry names a kind the schema has never heard of
        (TRC_EVENTS, 'QUERY_SCOPED_KINDS = ("span",)',
         'QUERY_SCOPED_KINDS = ("span", "ghost")'),
        # registered kind whose payload spec forgot to admit qid
        (TRC_EVENTS, '"span": (("name",), ("qid",)),',
         '"span": (("name",), ()),'),
        # stale registry entry: documented kind, no emit site left
        (TRC_EMITTER, '    log.emit("span", name="s", qid=qid)\n', ''),
        # registry must stay a parseable literal
        (TRC_EVENTS, 'QUERY_SCOPED_KINDS = ("span",)',
         'QUERY_SCOPED_KINDS = tuple(k for k in ("span",))'),
    ],
    ids=["missing-qid", "qid-via-star-blob", "unknown-kind",
         "payload-without-qid", "stale-entry", "computed-registry"],
)
def test_trace_context_fires(path, old, new):
    mutated = _mutate(TRACE_FIXTURE, path, old, new)
    fired = _rules(mutated, "trace-context")
    assert fired and set(fired) == {"trace-context"}, fired


# -- routing-hash ------------------------------------------------------------

RH_ROUTER = "dryad_tpu/serve/router.py"
RH_CLUSTER = "dryad_tpu/cluster/service.py"
RH_PLANNER = "dryad_tpu/plan/keys.py"

RH_ROUTER_CLEAN = '''\
import hashlib


def rendezvous_rank(fingerprint, replicas):
    key = fingerprint.encode()
    scored = [
        (hashlib.sha256(key + b"|" + rid.encode()).digest(), rid)
        for rid in replicas
    ]
    scored.sort(reverse=True)
    return [rid for _, rid in scored]
'''

RH_CLUSTER_CLEAN = '''\
class Mailbox:
    def set_prop(self, pid, name, value):
        self.key = (pid, name)
'''

RH_PLANNER_CLEAN = '''\
import hashlib


def stage_key(stage):
    fingerprint = hashlib.sha256(repr(stage).encode()).hexdigest()
    return fingerprint


def debug_tag(obj):
    # identity for log readability only — no routing name involved
    return id(obj)
'''

RH_FIXTURE = {
    RH_ROUTER: RH_ROUTER_CLEAN,
    RH_CLUSTER: RH_CLUSTER_CLEAN,
    RH_PLANNER: RH_PLANNER_CLEAN,
}


def test_routing_hash_clean_fixture():
    assert _rules(RH_FIXTURE, "routing-hash") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        # THE original hazard: tctx fingerprints derived from the
        # process-salted builtin — every front door disagrees
        (
            RH_ROUTER,
            "key = fingerprint.encode()",
            "key = str(hash(fingerprint)).encode()",
        ),
        # id() is an address, gone the moment the key crosses a pipe
        (
            RH_ROUTER,
            "key = fingerprint.encode()",
            "key = str(id(fingerprint)).encode()",
        ),
        # the transport tier is routing tier too: any hash() there
        (
            RH_CLUSTER,
            "self.key = (pid, name)",
            "self.key = hash((pid, name))",
        ),
        # project-wide: a routing-named ASSIGNMENT fed by hash()
        (
            RH_PLANNER,
            'fingerprint = hashlib.sha256(repr(stage).encode()).hexdigest()',
            "fingerprint = hash(repr(stage))",
        ),
        # project-wide: shard keys are routing keys by another name
        (
            RH_PLANNER,
            "def debug_tag(obj):",
            "def pick(obj, n):\n    shard_index = hash(obj) % n\n"
            "    return shard_index\n\n\ndef debug_tag(obj):",
        ),
        # project-wide: a fingerprint KEYWORD argument fed by id()
        (
            RH_PLANNER,
            "    return fingerprint",
            "    emit(fingerprint=id(stage))\n    return fingerprint",
        ),
        # anchor drift: the rendezvous router moving away must be loud
        (
            RH_ROUTER,
            "def rendezvous_rank(fingerprint, replicas):",
            "def hrw_rank(fingerprint, replicas):",
        ),
    ],
    ids=["hash-in-router", "id-in-router", "hash-in-cluster",
         "fingerprint-assign-hash", "shard-assign-hash",
         "fingerprint-kwarg-id", "anchor-drift"],
)
def test_routing_hash_fires(path, old, new):
    _assert_fires(_mutate(RH_FIXTURE, path, old, new), "routing-hash")


def test_routing_hash_shadowed_builtin_is_silent():
    """A module that rebinds hash()/id() owns the name — whatever the
    local function does, it is not the builtin salt hazard."""
    shadowed = _mutate(
        RH_FIXTURE,
        RH_PLANNER,
        "def debug_tag(obj):",
        "def hash(x):\n    return 7\n\n\n"
        "def local_route(x):\n    route_key = hash(x)\n"
        "    return route_key\n\n\ndef debug_tag(obj):",
    )
    assert _rules(shadowed, "routing-hash") == []


def test_routing_hash_plain_id_outside_key_names_is_silent():
    """id() for log readability (no routing-named sink) stays legal
    outside the routing tier — the project-wide scope only bites when
    the NAME says the value routes."""
    assert _rules(RH_FIXTURE, "routing-hash") == []
    ok = _mutate(
        RH_FIXTURE,
        RH_PLANNER,
        "    return id(obj)",
        "    tag = id(obj)\n    return tag",
    )
    assert _rules(ok, "routing-hash") == []


# -- view-state-discipline ---------------------------------------------------

VSD_MATVIEW = "dryad_tpu/views/matview.py"
VSD_ENGINE = "dryad_tpu/exec/outofcore.py"
VSD_SERVE = "dryad_tpu/serve/service.py"

VSD_MATVIEW_CLEAN = '''\
from dryad_tpu.exec.partial import merge_state_rows


class MaterializedView:
    def fold_delta(self, arrays):
        self.state = merge_state_rows(arrays, ["k"], {"s__p": "sum"})


def finalize_query(view, ctx):
    q = ctx.from_arrays(view.state_table())
    gq = q.group_by(["k"], {"s": ("sum", "s__p")})
    return gq
'''

VSD_ENGINE_CLEAN = '''\
from dryad_tpu.exec.partial import state_reductions


def drain(plan):
    return state_reductions(plan)
'''

VSD_SERVE_CLEAN = '''\
from dryad_tpu.views import ViewRegistry


def build(ctx):
    return ViewRegistry(ctx)
'''

VSD_FIXTURE = {
    VSD_MATVIEW: VSD_MATVIEW_CLEAN,
    VSD_ENGINE: VSD_ENGINE_CLEAN,
    VSD_SERVE: VSD_SERVE_CLEAN,
}


def test_view_state_discipline_clean_fixture():
    assert _rules(VSD_FIXTURE, "view-state-discipline") == []


@pytest.mark.parametrize(
    "path,old,new",
    [
        # views/ reaching into the gang driver inverts the layering
        (
            VSD_MATVIEW,
            "from dryad_tpu.exec.partial import merge_state_rows",
            "from dryad_tpu.exec.partial import merge_state_rows\n"
            "from dryad_tpu.cluster import gang",
        ),
        # views -> serve is a cycle through serve/__init__
        (
            VSD_MATVIEW,
            "from dryad_tpu.exec.partial import merge_state_rows",
            "from dryad_tpu.exec.partial import merge_state_rows\n"
            "from dryad_tpu.serve.cache import ResultCache",
        ),
        # the engine must not know views exist
        (
            VSD_ENGINE,
            "from dryad_tpu.exec.partial import state_reductions",
            "from dryad_tpu.exec.partial import state_reductions\n"
            "from dryad_tpu.views import ViewRegistry",
        ),
        # a second finalization path: group_by plan built in the fold
        (
            VSD_MATVIEW,
            '        self.state = merge_state_rows('
            'arrays, ["k"], {"s__p": "sum"})',
            '        self.state = merge_state_rows('
            'arrays, ["k"], {"s__p": "sum"})\n'
            '        self.snap = self.q.group_by(["k"], {})',
        ),
        # finalize_fn called outside the snapshot path
        (
            VSD_MATVIEW,
            '        self.state = merge_state_rows('
            'arrays, ["k"], {"s__p": "sum"})',
            '        self.state = merge_state_rows('
            'arrays, ["k"], {"s__p": "sum"})\n'
            "        self.fin = finalize_fn(self.plan)",
        ),
        # views/ executing directly — even inside the anchor
        (
            VSD_MATVIEW,
            "    return gq",
            "    return ctx.run_to_host(gq)",
        ),
        # anchor drift: the snapshot path moving away must be loud
        (
            VSD_MATVIEW,
            "def finalize_query(view, ctx):",
            "def snapshot_plan(view, ctx):",
        ),
    ],
    ids=["views-imports-cluster", "views-imports-serve",
         "engine-imports-views", "group-by-outside-anchor",
         "finalize-fn-outside-anchor", "exec-in-views", "anchor-drift"],
)
def test_view_state_discipline_fires(path, old, new):
    _assert_fires(_mutate(VSD_FIXTURE, path, old, new),
                  "view-state-discipline")
