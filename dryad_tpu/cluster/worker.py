"""Worker process for N-process local jobs — the VertexHost analog.

The reference's worker node runs a long-lived daemon whose children poll
a versioned property mailbox for a ``DVertexCommand``, execute the
vertex, and post ``DVertexStatus`` back (``dvertexpncontrol.h:38-70``;
mailbox ``ProcessService.cs:42-126``).  This module is the TPU-native
worker: one OS process per mesh *slice* that

1. joins the JAX multi-controller runtime (``jax.distributed``) so the
   N workers' devices form ONE global mesh and compiled programs
   gang-launch across processes (cross-process collectives ride gloo on
   CPU, ICI/DCN on TPU),
2. announces itself on the driver's ProcessService control plane
   (membership + heartbeats, ``ControlPlane``),
3. loops on its ``cmd/<pid>`` mailbox property: a ``run`` command names
   a job package on the driver's file server; every worker executes the
   SAME SPMD plan jointly, then writes the partitions it *owns* (its
   addressable shards) as partition files for the driver to assemble —
   the persisted-channel-file egress of the reference
   (``DrPartitionFile.h:50``), and posts ``status/<pid>``.

Run as ``python -m dryad_tpu.cluster.worker --service-port P --job J
--pid I --nproc N --devices-per-proc K --coordinator H:P --root DIR``
(spawned by ``cluster.localjob.LocalJobSubmission``).
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import tempfile
import time
import traceback
from typing import Dict, List

from dryad_tpu.obs import tracectx


class _PackageCache:
    """Per-process cache of loaded job packages for vertex tasks.

    A ``runpart`` stream re-uses one loaded plan + context (and its
    compiled-stage cache) across partitions — the reference's VertexHost
    similarly keeps the vertex DLL loaded across vertex executions."""

    def __init__(self) -> None:
        self.key: str = ""
        self.query = None
        self.pristine: Dict = {}

    def load(self, rel: str, client):
        if self.key == rel and self.query is not None:
            return self.query, self.pristine
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from dryad_tpu.exec.jobpackage import load_query
        from dryad_tpu.parallel.mesh import AXIS

        blob = client.read_whole_file(rel)
        with tempfile.NamedTemporaryFile(suffix=".pkg", delete=False) as fh:
            fh.write(blob)
            pkg_path = fh.name
        try:
            # Vertex tasks run on ONE local device — independent work,
            # not the gang mesh (DrStorageVertex-style per-partition
            # channels, not cohort collectives).
            local = Mesh(np.array(jax.local_devices()[:1]), (AXIS,))
            q = load_query(pkg_path, mesh=local)
        finally:
            os.unlink(pkg_path)
        self.key = rel
        self.query = q
        self.pristine = q.ctx.inputs.snapshot()
        return q, self.pristine


def _bind_part(q, pristine: Dict, part: int, nparts: int) -> None:
    """Rebind every input of the package to its part ``part`` of
    ``nparts`` (``Inputs.restore`` drops each node's fingerprint and
    device entry: a stale part-0 fingerprint would make checkpointing
    restore part 0 for every part)."""
    q.ctx.inputs.restore(
        {nid: b.part(part, nparts) for nid, b in pristine.items()}
    )


def _run_part(cmd: Dict, args, client, pkgs: _PackageCache,
              pcache=None) -> Dict:
    """Execute ONE vertex task: the plan restricted to input partition
    ``part`` of ``nparts``, on this worker's local device, writing the
    result as a partition file (the independent re-executable vertex of
    the reference, ``DrVertex.h:49`` — duplicate-safe: every attempt
    writes identical bytes and the rename is atomic)."""
    import numpy as np

    from dryad_tpu.cluster.partcache import content_fp
    from dryad_tpu.columnar.io import write_partition_file

    q, pristine = pkgs.load(cmd["package"], client)
    part, nparts = int(cmd["part"]), int(cmd["nparts"])
    _bind_part(q, pristine, part, nparts)
    batch = q.ctx._execute_device(q)
    valid = np.asarray(batch.valid)
    cols = {c: np.asarray(v)[valid] for c, v in batch.data.items()}
    out_dir = os.path.join(args.root, cmd["result_dir"])
    os.makedirs(out_dir, exist_ok=True)
    final = os.path.join(out_dir, f"part{part}.dpf")
    tmp = f"{final}.w{args.pid}.tmp"
    write_partition_file(tmp, cols)
    # fingerprint the serialized bytes BEFORE the rename (duplicates
    # write identical bytes, so every attempt reports the same fp) and
    # keep them gang-resident: a later sub-command naming this
    # partition by fp (level -1 combineparts) reads it from memory
    # instead of the job root
    with open(tmp, "rb") as fh:
        blob = fh.read()
    fp = content_fp(blob)
    os.replace(tmp, final)
    if pcache is not None:
        pcache.put(fp, blob)
    return {"state": "completed", "parts": [part], "fp": fp}


def _combine_parts(cmd: Dict, args, client, pkgs: _PackageCache,
                   pcache=None, wlog=None) -> Dict:
    """Level -1 of the gang combine tree: fold the un-finalized partial
    STATE of the vertex parts THIS worker won into one partial table
    (``exec.partial.merge_state_rows``) before anything ships to the
    driver — the reference's dynamic aggregation-tree rewrite
    (``DrDynamicAggregateManager.h:117-168``) pushed into the worker.
    Ships one ``wpart<w>.dpf`` plus a KeyRangeHistogram snapshot over
    DETERMINISTIC key hashes (``exec.partial.key_hash64`` — snapshots
    must mean the same ranges in every process), so driver ingress
    drops by this worker's vertex fan-in and the driver's level-0/1
    tree starts from per-worker partials.  Part bytes resolve through
    the :class:`~dryad_tpu.cluster.partcache.PartitionCache` by
    content fingerprint — this worker wrote them moments ago, so the
    common case never touches the job root."""
    import numpy as np

    from dryad_tpu.cluster.partcache import content_fp
    from dryad_tpu.columnar.batch import decode_physical_table
    from dryad_tpu.columnar.io import (
        parse_partition_bytes,
        write_partition_file,
    )
    from dryad_tpu.exec import faults
    from dryad_tpu.exec.partial import key_hash64, merge_state_rows
    from dryad_tpu.obs.metrics import KeyRangeHistogram

    faults.registry.maybe_fail("combineparts")
    if faults.registry.maybe_kill("combineparts"):
        # mid-level-(-1) chaos: the process dies between winning its
        # parts and shipping the folded partial — the driver must fall
        # back to flat assembly (the part files are durable) and the
        # blackbox must be on disk before the process vanishes
        from dryad_tpu.obs import flightrec

        if wlog is not None:
            wlog.emit(
                "worker_killed_injected", stage=-1, name="combineparts"
            )
        flightrec.dump_now("worker_killed:combineparts")
        os._exit(113)

    q, _pristine = pkgs.load(cmd["package"], client)
    keys = list(cmd["keys"])
    red = dict(cmd["red"])
    tables = []
    read_bytes = 0
    hits = misses = 0
    for spec in cmd["parts"]:
        blob = None
        fp = spec.get("fp")
        if pcache is not None and fp:
            blob = pcache.get(fp)
        if blob is None:
            misses += 1
            blob = client.read_whole_file(
                f"{cmd['result_dir']}/part{spec['part']}.dpf"
            )
            read_bytes += len(blob)
            if pcache is not None:
                pcache.put(fp or content_fp(blob), blob)
        else:
            hits += 1
        host = parse_partition_bytes(blob)
        # decode to logical columns WITHOUT the dictionary: string keys
        # stay raw Hash64 codes (cross-process deterministic), so the
        # fold groups on codes and the driver decodes once at assembly
        tables.append(
            decode_physical_table(q.schema, slice(None), host, None)
        )
    cols = {c: np.concatenate([t[c] for t in tables]) for c in tables[0]}
    in_rows = int(len(next(iter(cols.values()), [])))
    merged = merge_state_rows(cols, keys, red)
    out_rows = int(len(merged[keys[0]])) if keys else 0
    kr = KeyRangeHistogram(int(cmd.get("ranges", 64) or 64))
    if keys and out_rows:
        kr.observe(key_hash64(merged, keys))
    out_dir = os.path.join(args.root, cmd["result_dir"])
    os.makedirs(out_dir, exist_ok=True)
    wname = f"wpart{int(cmd['wid'])}.dpf"
    final = os.path.join(out_dir, wname)
    tmp = f"{final}.w{args.pid}.tmp"
    write_partition_file(tmp, merged)
    with open(tmp, "rb") as fh:
        out_blob = fh.read()
    out_fp = content_fp(out_blob)
    os.replace(tmp, final)
    if pcache is not None:
        pcache.put(out_fp, out_blob)
    snap = {
        k: (v.tolist() if hasattr(v, "tolist") else v)
        for k, v in kr.snapshot().items()
    }
    return {
        "state": "completed", "wfile": wname, "fp": out_fp,
        "parts": [int(s["part"]) for s in cmd["parts"]],
        "rows": out_rows, "in_rows": in_rows,
        "bytes": len(out_blob), "read_bytes": read_bytes,
        "cache_hits": hits, "cache_misses": misses,
        "snapshot": snap,
    }


def _run_coded(cmd: Dict, args, client, pkgs: _PackageCache) -> Dict:
    """Execute ONE CODED vertex (``dryad_tpu.redundancy``): run the
    partial plan over each shard in the vertex's support, linearly
    combine the partial tables with the generator coefficients
    (``exec.partial.coded_combine`` — exact int64 for integer states),
    and write the coded partial as ``cpart<j>.dpf``.  A systematic
    vertex (support of one shard, coefficient 1) does exactly one
    shard's work; a parity vertex pays the full-support redundancy
    work that buys any-k-of-n reconstruction."""
    from dryad_tpu.columnar.io import write_partition_file
    from dryad_tpu.exec.partial import coded_combine

    q, pristine = pkgs.load(cmd["package"], client)
    nparts = int(cmd["nparts"])
    tables = []
    for part in cmd["parts"]:
        _bind_part(q, pristine, int(part), nparts)
        batch = q.ctx._execute_device(q)
        tables.append(batch.to_numpy(q.schema, q.ctx.dictionary))
    combined = coded_combine(
        tables, [int(c) for c in cmd["coeffs"]],
        list(cmd["keys"]), list(cmd["state"]),
    )
    out_dir = os.path.join(args.root, cmd["result_dir"])
    os.makedirs(out_dir, exist_ok=True)
    j = int(cmd["coded"])
    final = os.path.join(out_dir, f"cpart{j}.dpf")
    tmp = f"{final}.w{args.pid}.tmp"
    write_partition_file(tmp, combined)
    os.replace(tmp, final)
    return {"state": "completed", "coded": [j]}


def _absorb_ctx_events(wlog, ctx) -> None:
    """Move the job context's engine events (stage spans, xla_compile,
    stream events) into the worker's telemetry log so they ship to the
    driver with the next batch."""
    if wlog is None or ctx is None:
        return
    for ev in ctx.events.drain():
        wlog.absorb(ev)


def _run_command(cmd: Dict, args, client, cp, wlog=None) -> Dict:
    """Execute one ``run`` command: fetch the package, run the plan SPMD
    over the global mesh, write owned result partitions."""
    import numpy as np

    from dryad_tpu.columnar.io import write_partition_file
    from dryad_tpu.exec.jobpackage import load_query
    from dryad_tpu.parallel.mesh import make_mesh, num_partitions

    # Fetch the package through the driver's file server (HTTP range
    # reads via the block cache — the managed-channel read path).
    blob = client.read_whole_file(cmd["package"])
    with tempfile.NamedTemporaryFile(suffix=".pkg", delete=False) as fh:
        fh.write(blob)
        pkg_path = fh.name
    try:
        mesh = make_mesh(args.nproc * args.devices_per_proc)
        q = load_query(pkg_path, mesh=mesh)
        ctx = q.ctx
        # Everyone present before tracing/ingest: a straggler joining
        # mid-collective would deadlock the gang, so gate here where the
        # failure is a clean timeout instead (DrStartClique semantics).
        cp.barrier(f"start/{cmd['seq']}", args.nproc)
        batch = ctx._execute_device(q)
        P = num_partitions(mesh)
        cap = batch.capacity // P

        out_dir = os.path.join(args.root, cmd["result_dir"])
        os.makedirs(out_dir, exist_ok=True)
        # Each addressable shard of the result IS one owned partition;
        # write its valid rows as a partition file.
        vshards = {
            int(s.index[0].start or 0): np.asarray(s.data)
            for s in batch.valid.addressable_shards
        }
        col_shards = {
            c: {
                int(s.index[0].start or 0): np.asarray(s.data)
                for s in arr.addressable_shards
            }
            for c, arr in batch.data.items()
        }
        parts: List[int] = []
        for start in sorted(vshards):
            gid = start // cap
            mask = vshards[start]
            cols = {c: col_shards[c][start][mask] for c in col_shards}
            write_partition_file(
                os.path.join(out_dir, f"part{gid}.dpf"), cols
            )
            parts.append(gid)
        if args.pid == 0:
            # The dictionary is built at ingest (identically in every
            # worker); ship one copy so the driver can decode strings.
            with open(os.path.join(out_dir, "dictionary.pkl"), "wb") as fh:
                pickle.dump(dict(ctx.dictionary._map), fh)
        # All partitions durable before anyone reports success — the
        # driver may start reading as soon as one status arrives.
        cp.barrier(f"done/{cmd['seq']}", args.nproc)
        _absorb_ctx_events(wlog, ctx)
        return {"state": "completed", "parts": parts}
    finally:
        os.unlink(pkg_path)


def _resolve_pcache(pstate: Dict, cmd: Dict, args):
    """Lazily build this worker's :class:`PartitionCache` the first time
    a command carries a ``cache_bytes`` budget (the driver forwards
    ``config.gang_partition_cache_bytes``); a zero/absent budget runs
    the command cache-less without disturbing an existing cache."""
    budget = int(cmd.get("cache_bytes", 0) or 0)
    if budget <= 0:
        return None
    pc = pstate.get("pcache")
    if pc is None:
        from dryad_tpu.cluster.partcache import PartitionCache

        pc = PartitionCache(
            budget,
            spill_dir=os.path.join(args.root, f".pcache-w{args.pid}"),
        )
        pstate["pcache"] = pc
    return pc


def _exec_one(cmd: Dict, args, client, cp, pkgs, delay, wtracer, wlog,
              pstate=None) -> Dict:
    """Execute one run/runpart/runcoded/combineparts command and return
    its status dict (no cseq — the caller stamps the mailbox echo).
    Failures are classified per command: a failed status carries the
    error, and the worker keeps serving (report-and-continue, never
    crash the loop)."""
    pstate = pstate if pstate is not None else {}
    try:
        # Re-activate the query's trace context from the mailbox
        # envelope: every span this command produces (and the engine
        # events absorbed from the job context) ships back qid-stamped
        # on the telemetry channel, joining the driver's fold.
        with tracectx.activate(
            tracectx.TraceContext.from_wire(cmd.get("trace"))
        ), wtracer.span(
            cmd["kind"], cat="worker", seq=cmd.get("seq"),
            part=cmd.get("part", cmd.get("coded")),
        ):
            if cmd["kind"] in ("runpart", "runcoded"):
                # injected straggler applies to coded vertices too, so
                # coded-vs-duplicate comparisons stall the same way
                if delay["count"] > 0:
                    delay["count"] -= 1
                    time.sleep(delay["seconds"])
                status = (
                    _run_part(cmd, args, client, pkgs,
                              pcache=_resolve_pcache(pstate, cmd, args))
                    if cmd["kind"] == "runpart"
                    else _run_coded(cmd, args, client, pkgs)
                )
                _absorb_ctx_events(
                    wlog,
                    pkgs.query.ctx if pkgs.query is not None else None,
                )
            elif cmd["kind"] == "combineparts":
                status = _combine_parts(
                    cmd, args, client, pkgs,
                    pcache=_resolve_pcache(pstate, cmd, args), wlog=wlog,
                )
            else:
                status = _run_command(cmd, args, client, cp, wlog=wlog)
    except Exception as e:  # noqa: BLE001 — report, keep serving
        traceback.print_exc()
        info = {"error": f"{type(e).__name__}: {e}", "cmd": cmd}
        cp.report_failure(info)
        status = {"state": "failed", "error": info["error"]}
    return status


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--service-host", default="127.0.0.1")
    ap.add_argument("--service-port", type=int, required=True)
    ap.add_argument("--job", required=True)
    ap.add_argument("--pid", type=int, required=True)
    ap.add_argument("--nproc", type=int, required=True)
    ap.add_argument("--devices-per-proc", type=int, default=1)
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--root", required=True)
    args = ap.parse_args(argv)

    # Backend setup MUST precede any backend query: pin CPU with K local
    # devices, select gloo for cross-process CPU collectives, then join
    # the multi-controller runtime.  Gang workers run on CPU devices
    # only today (one worker per chip is ROADMAP S6).
    from dryad_tpu.parallel.mesh import force_cpu_backend

    force_cpu_backend(args.devices_per_proc)
    import jax

    if args.nproc > 1:
        # gloo needs the distributed client; a single-member gang never
        # initializes one (init_distributed no-ops at nproc<=1), and
        # some jaxlibs refuse gloo without it — so only select it when
        # cross-process collectives will actually exist.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    from dryad_tpu.parallel.multihost import ControlPlane, init_distributed

    init_distributed(args.coordinator, args.nproc, args.pid)

    from dryad_tpu.cluster.service import ServiceClient

    client = ServiceClient(args.service_host, args.service_port)
    cp = ControlPlane(args.job, args.pid, client=client)
    cp.announce({"devices": args.devices_per_proc, "ospid": os.getpid()})
    cp.start_heartbeat()

    # Worker-local telemetry (obs): spans around command execution plus
    # the job context's engine events, shipped back to the driver
    # through the ControlPlane mailbox after every command — the
    # reporter-inside-the-GM analog, aggregated in cluster.localjob.
    from dryad_tpu.exec.events import EventLog
    from dryad_tpu.obs.span import Tracer

    wlog = EventLog(None, mem_cap=8192)
    wtracer = Tracer(wlog)

    # Flight recorder (obs.flightrec): the worker's ring survives what
    # telemetry shipping cannot — a process death takes un-shipped
    # events with it, so the ring dumps to the SHARED job root
    # (blackbox-<ospid>.json) on any exit: atexit, SIGTERM, unhandled
    # exceptions, and the chaos os._exit path (dumped explicitly by
    # the executor before _exit).  tools/blackbox.py merges these with
    # the driver's dump into one clock-corrected timeline.
    from dryad_tpu.obs import flightrec

    flightrec.install_recorder(
        capacity=2048,
        snapshot_s=1.0,
        dump_dir=os.path.join(args.root, "blackbox"),
        role=f"worker-{args.pid}",
        worker=args.pid,
        events=wlog,
        atexit_dump=True,
        signals=True,
    )
    flightrec.get_recorder().set_info(job=args.job, nproc=args.nproc)

    after = 0
    pkgs = _PackageCache()
    pstate: Dict = {}  # lazy PartitionCache, keyed setup per job
    delay = {"seconds": 0.0, "count": 0}  # injected straggler behavior
    while True:
        got = client.get_prop(args.job, f"cmd/{args.pid}", after, timeout=2.0)
        if got is None:
            continue
        after, body = got
        cmd = json.loads(body)
        # Every status echoes the command's unique id ("cseq") so the
        # driver can discard stale statuses from a command it already
        # gave up on (e.g. a run that outlived its timeout).
        cseq = cmd.get("cseq")
        if cmd.get("ack"):
            # Windowed envelope: acknowledge the DEQUEUE itself, before
            # executing — the command mailbox is a latest-value slot,
            # and the overlapped feed may only overwrite it once this
            # envelope has provably left it.
            try:
                client.set_prop(args.job, str(cmd["ack"]), b"1")
            except Exception:  # noqa: BLE001 — driver timeout surfaces it
                pass
        if cmd["kind"] == "exit":
            client.set_prop(
                args.job, f"status/{args.pid}",
                json.dumps({"state": "exited", "cseq": cseq}).encode(),
            )
            cp.stop_heartbeat()
            return 0
        if cmd["kind"] == "set_fault":
            # Remote fault injection (SetFakeVertexFailure over the
            # command mailbox).  Stage faults must reach EVERY gang
            # member (a fault raised in only some would strand the
            # others in a collective); a seeded FaultPlan — including
            # worker_kill_prob process kills, the mid-collective-death
            # chaos scenario — may target a worker subset, where
            # stranding the peers is exactly what is under test.
            from dryad_tpu.exec import faults

            if cmd.get("plan"):
                faults.install_plan(faults.FaultPlan(**cmd["plan"]))
            elif cmd.get("stage"):
                faults.set_fake_stage_failure(
                    cmd["stage"], int(cmd.get("count", 1))
                )
            else:
                faults.clear_faults()
            client.set_prop(
                args.job, f"status/{args.pid}",
                json.dumps({"state": "fault_set", "cseq": cseq}).encode(),
            )
            continue
        if cmd["kind"] == "set_delay":
            # Injected straggler (per-worker, unlike set_fault's gang
            # broadcast): the next ``count`` vertex tasks on THIS worker
            # stall ``seconds`` before executing — the slow-machine
            # scenario speculative duplication exists for
            # (``DrStageStatistics.cpp:93`` outlier model).
            delay["seconds"] = float(cmd.get("seconds", 0.0))
            delay["count"] = int(cmd.get("count", 0))
            client.set_prop(
                args.job, f"status/{args.pid}",
                json.dumps({"state": "delay_set", "cseq": cseq}).encode(),
            )
            continue
        if cmd["kind"] == "runbatch":
            # Batched command stream: execute the sub-commands
            # back-to-back and ship ONE aggregated status — K mailbox
            # round trips become one (the cseq echo covers the batch).
            # A failed sub-command does NOT stop the batch: every gang
            # member executes the same list in the same order, keeping
            # the per-command start/done barriers aligned, and the
            # per-command statuses preserve fault classification.
            results = []
            first_error = None
            for sub in cmd["cmds"]:
                # envelope-level trace context covers sub-commands that
                # didn't carry their own
                if cmd.get("trace") and not sub.get("trace"):
                    sub["trace"] = cmd["trace"]
                sub_t0 = time.perf_counter()
                st = _exec_one(sub, args, client, cp, pkgs, delay,
                               wtracer, wlog, pstate=pstate)
                # per-sub wall clock rides in the aggregated status so
                # the driver's StageStatistics sees K real durations,
                # not one batch-wide dt smeared across K plans
                st["seconds"] = round(time.perf_counter() - sub_t0, 6)
                results.append(st)
                if st.get("state") == "failed" and first_error is None:
                    first_error = st.get("error")
            status = {
                "state": "failed" if first_error else "completed",
                "results": results,
            }
            if first_error:
                status["error"] = first_error
        elif cmd["kind"] in ("run", "runpart", "runcoded", "combineparts"):
            status = _exec_one(cmd, args, client, cp, pkgs, delay,
                               wtracer, wlog, pstate=pstate)
        else:
            continue  # unknown command kind: ignore, keep serving
        # telemetry ships BEFORE the status post: the driver drains
        # right after it sees the status, so shipping after would
        # race the batch against the drain
        try:
            cp.ship_telemetry(wlog.drain())
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass
        status["cseq"] = cseq
        client.set_prop(
            args.job, cmd.get("skey") or f"status/{args.pid}",
            json.dumps(status).encode(),
        )


if __name__ == "__main__":
    sys.exit(main())
