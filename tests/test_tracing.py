"""Causal tracing + blame attribution: the exactness invariant (blame
categories sum to e2e), DES transparency (tracing reproduces latencies
byte-for-byte), Chrome trace-event export schema, sampling/retention
bounds, and the 50k-event overhead envelope.

The hypothesis property variant (random chain graphs) is marked slow and
runs in the dedicated CI slow job; everything else is tier-1.
"""
import gc
import json
import time

import pytest

from repro.core import CascadeStore
from repro.core.affinity import instance_of
from repro.runtime import (Compute, FaultInjector, Put, Runtime,
                           TraceConfig, TraceRecorder)
from repro.runtime.tracing import CATEGORIES, InstanceTrace
from repro.workflows import (BlameTable, Emit, WorkflowGraph,
                             WorkflowRuntime, critical_path, decompose,
                             mode_kwargs, preload_index)

RES = {"gpu": 1, "cpu": 2, "nic": 2}
SHAPES = ("rag", "speech")
MODES = ("keyhash", "atomic", "atomic+batch", "atomic+abatch")
DEADLINES = {"rag": 0.30, "speech": 0.20}


def _shape_run(shape, mode, faults=False, tracing=True, n=16, shards=2,
               seed=0, rate=None):
    from repro.workflows import WORKFLOW_SHAPES
    graph = WORKFLOW_SHAPES[shape](shards=shards)
    wrt = WorkflowRuntime(graph, seed=seed, tracing=tracing,
                          **mode_kwargs(mode))
    if shape == "rag":
        preload_index(wrt)
    if faults:
        inj = wrt.enable_faults()
        inj.fail_node(sorted(wrt.rt.nodes)[0], at=0.08, duration=0.1)
    rate = rate if rate is not None else 12.0 * shards
    for i in range(n):
        wrt.submit(f"req{i}", at=0.05 + i / rate,
                   deadline=DEADLINES[shape])
    wrt.run()
    return wrt


# -- the exactness invariant --------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("faults", (False, True))
def test_blame_sums_to_e2e_and_path_is_contiguous(shape, mode, faults):
    """Across workflow shapes x placement/batching modes x fault
    injection, every completed trace decomposes into exclusive category
    durations that sum to the end-to-end latency within 1e-6, and the
    critical path partitions [t_submit, t_complete] contiguously."""
    wrt = _shape_run(shape, mode, faults=faults)
    traces = wrt.tracer.traces()
    assert len(traces) == 16                    # all sampled + retained
    for tr in traces:
        parts = decompose(tr)
        assert set(parts) == set(CATEGORIES)
        assert all(v >= 0.0 for v in parts.values()), parts
        assert abs(sum(parts.values()) - tr.e2e) < 1e-6, (tr.instance,
                                                          parts, tr.e2e)
        segs = critical_path(tr)
        assert segs[0][2] == tr.t_submit
        assert segs[-1][3] == tr.t_complete
        for a, b in zip(segs, segs[1:]):
            assert a[3] == b[2], (a, b)
    # the on_complete aggregate saw the same population
    assert wrt.blame.n == wrt.tracer.n_completed == 16
    assert abs(sum(wrt.blame.totals.values())
               - wrt.blame.e2e_total) < 1e-6


def test_compute_dominates_an_unloaded_run():
    """At trivial load the blame table should charge mostly compute —
    a sanity anchor that categorization is not arbitrary."""
    wrt = _shape_run("rag", "atomic", n=4)
    assert wrt.blame.dominant() == "compute"
    assert wrt.blame.shares()["compute"] > 0.5


def test_fault_stall_is_blamed_under_unwired_chaos():
    """An unwired node death (no repair layer) stalls pinned work; the
    stall time must land in ``fault_stall``, not ``queueing``."""
    g = WorkflowGraph("chaos")
    g.add_tier("t", 2, RES)
    g.add_pool("/in", tier="t", shards=2)
    g.add_pool("/out", tier="t", shards=2)
    g.add_stage("work", pool="/in", resource="gpu", cost=0.004,
                emits=[Emit("/out", fanout=1, size=1024)], sink=True)
    wrt = WorkflowRuntime(g.validate(), tracing=True,
                          **mode_kwargs("atomic"))
    inj = FaultInjector(wrt.rt)                 # raw: nothing re-pins
    inj.fail_node(sorted(wrt.rt.nodes)[0], at=0.06, duration=0.2)
    for i in range(24):
        wrt.submit(f"w{i}", at=0.05 + i * 0.002)
    wrt.run()
    assert wrt.summary()["n"] == 24
    assert wrt.blame.totals["fault_stall"] > 0.0
    # the down/up window reached the recorder as global instants
    names = [n for n, _, _ in wrt.tracer.global_events]
    assert "node_down" in names and "node_up" in names


def test_blame_sums_to_e2e_with_partition_active():
    """Exactness survives a network cut: work held at the partition
    boundary surfaces as ``partition_stall`` (not silently as network or
    other), and every trace still decomposes to its e2e exactly."""
    g = WorkflowGraph("cut")
    g.add_tier("t", 4, RES)
    for p in ("/in", "/out"):
        g.add_pool(p, tier="t", shards=4)
    g.add_stage("work", pool="/in", resource="gpu", cost=0.004,
                emits=[Emit("/out", fanout=1, size=1024)], sink=True)
    wrt = WorkflowRuntime(g.validate(), read_replicas=2, tracing=True,
                          **mode_kwargs("affinity"))
    inj = wrt.enable_faults()
    # cut half the tier off mid-stream: groups whose every replica lane
    # sits across the cut park their dispatches until heal
    inj.partition(((), ("t1", "t3")), at=0.06, duration=0.2)
    for i in range(40):
        wrt.submit(f"w{i}", at=0.05 + i * 0.002)
    wrt.run()
    assert wrt.summary()["n"] == 40                     # nothing lost
    assert wrt.rt.sim.partition_parked_dispatches > 0   # the cut bit
    for tr in wrt.tracer.traces():
        parts = decompose(tr)
        assert set(parts) == set(CATEGORIES)
        assert all(v >= 0.0 for v in parts.values()), parts
        assert abs(sum(parts.values()) - tr.e2e) < 1e-6, (tr.instance,
                                                          parts, tr.e2e)
    assert wrt.blame.totals["partition_stall"] > 0.0
    assert abs(sum(wrt.blame.totals.values())
               - wrt.blame.e2e_total) < 1e-6


# -- DES transparency ---------------------------------------------------------

def _chaos_summary(tracing):
    g = WorkflowGraph("chaos")
    g.add_tier("t", 3, RES)
    for p in ("/in", "/mid", "/out"):
        g.add_pool(p, tier="t", shards=3)
    g.add_stage("prep", pool="/in", resource="cpu", cost=0.002,
                emits=[Emit("/mid", fanout=1, size=4096)])
    g.add_stage("infer", pool="/mid", resource="gpu", cost=0.008,
                emits=[Emit("/out", fanout=1, size=1024)], sink=True)
    wrt = WorkflowRuntime(g.validate(), read_replicas=2,
                          hedge_after=0.03, tracing=tracing,
                          **mode_kwargs("atomic+abatch"))
    inj = wrt.enable_faults()
    inj.fail_node("t0", at=0.2, duration=0.1)
    for i in range(60):
        wrt.submit(f"r{i}", at=0.05 + i / 200.0, deadline=0.12)
    wrt.run()
    return wrt


def test_tracing_reproduces_latencies_byte_for_byte():
    """The observability layer only observes: enabling tracing on a
    chaos run (faults + repair + replicas + hedging + adaptive batching)
    must not move a single latency, event count, or hedge."""
    off = _chaos_summary(tracing=False)
    on = _chaos_summary(tracing=True)
    assert off.rt.sim.tracer is None
    s_off, s_on = off.summary(), on.summary()
    for k in ("n", "median", "p95", "p99", "slo_miss_rate"):
        assert s_off[k] == s_on[k], k
    assert off.rt.sim.events_fired == on.rt.sim.events_fired
    assert off.rt.hedges == on.rt.hedges
    # and the traced run carries the observability keys the untraced
    # one must not pay for
    assert "blame_top" in s_on and "blame_top" not in s_off
    assert s_on["traces_completed"] == s_on["n"]
    if on.rt.hedges:
        hedge_marks = sum(1 for tr in on.tracer.traces()
                          for name, _, _ in tr.events
                          if name.startswith("hedge:"))
        assert hedge_marks > 0


def test_batched_stage_emits_exact_batch_spans():
    """A batched stage's member traces carry the batcher's exact
    decomposition: formation wait, queue wait, and the shared compute
    interval — never a generic barrier for the batch future."""
    wrt = _shape_run("rag", "atomic+batch", n=32, rate=400.0)
    cats = {}
    for tr in wrt.tracer.traces():
        for sp in tr.spans:
            cats.setdefault(sp.name.split(":")[0], set()).add(sp.cat)
    assert cats.get("batch") == {"compute"}
    assert cats.get("batchform") == {"batch_wait"}
    assert "wait" not in cats                   # batch futures skipped


# -- admission control --------------------------------------------------------

def test_admission_defer_time_is_blamed():
    """A deferred admission opens the trace window at the ORIGINAL
    submit time: the defer shows up as an ``admission_defer`` span and
    the trace e2e covers it even though the tracker's latency restarts
    at the admission instant."""
    from repro.runtime import GPU_A100, GPU_H100, AutoscalePolicy
    g = WorkflowGraph("elastic")
    g.add_tier("fast", 1, RES, profile=GPU_H100)
    g.add_tier("slow", 0, RES, profile=GPU_A100, spares=1)
    for p in ("/in", "/out"):
        g.add_pool(p, tier=("fast", "slow"), shards=1)
    g.add_stage("work", pool="/in", resource="gpu", cost=0.02,
                emits=[Emit("/out", fanout=1, size=1024)], sink=True)
    wrt = WorkflowRuntime(g.validate(), admission="defer",
                          admission_defer=0.02, admission_max_defer=0.5,
                          tracing=True, **mode_kwargs("atomic"))
    wrt.enable_autoscale(slo=0.2, policy=AutoscalePolicy(
        interval=0.02, min_samples=2, min_shards=1))
    for i in range(30):
        wrt.submit(f"w{i}", at=0.0)
    wrt.submit("d", at=0.001, deadline=0.3)
    wrt.run()
    assert wrt.summary()["admission_deferrals"] > 0
    tr = next(t for t in wrt.tracer.traces() if t.instance == "d")
    defer = [sp for sp in tr.spans if sp.cat == "admission_defer"]
    assert defer and defer[0].t0 == 0.001
    assert decompose(tr)["admission_defer"] > 0.0
    rec = wrt.tracker.records["d"]
    assert tr.e2e >= (rec.t_complete - rec.t_submit) - 1e-12


# -- sampling / retention -----------------------------------------------------

def test_sampling_is_a_deterministic_hash():
    a = TraceRecorder(TraceConfig(sample_rate=0.5))
    b = TraceRecorder(TraceConfig(sample_rate=0.5))
    ids = [f"req{i}" for i in range(400)]
    picks = [a.sampled(i) for i in ids]
    assert picks == [b.sampled(i) for i in ids]     # run-to-run stable
    assert 100 < sum(picks) < 300                   # ~rate, not degenerate
    none = TraceRecorder(TraceConfig(sample_rate=0.0))
    assert not any(none.sampled(i) for i in ids)
    assert none.begin("req0", 0.0) is None


def test_retention_is_bounded_and_tail_biased():
    rec = TraceRecorder(TraceConfig(max_traces=8, top_k=4))
    for i in range(200):
        tr = rec.begin(f"i{i}", 0.0)
        rec.complete(tr, (i % 100) * 1e-3)          # latency cycles 0..99ms
    assert rec.n_completed == 200 and not rec.live
    kept = rec.traces()
    assert len(kept) <= 8 + 4
    tail = rec.tail()
    assert len(tail) == 4
    assert [t.e2e for t in tail] == sorted((t.e2e for t in tail),
                                           reverse=True)
    assert tail[0].e2e == pytest.approx(0.099)      # the true max survives
    rec.complete(tail[0], 1.0)                      # idempotent
    assert rec.n_completed == 200


def test_blame_table_merge_matches_combined():
    def table(traces):
        t = BlameTable()
        for tr in traces:
            t.add(tr)
        return t

    def mk(i):
        tr = InstanceTrace(f"i{i}", 0.0)
        rec = TraceRecorder()
        rec.span(tr, "compute", "c", 0.0, 0.001 * (i + 1))
        rec.span(tr, "queueing", "q", 0.001 * (i + 1), 0.002 * (i + 1))
        tr.t_complete = 0.002 * (i + 1)
        return tr

    traces = [mk(i) for i in range(20)]
    combined = table(traces)
    merged = table(traces[:7]).merge(table(traces[7:]))
    assert merged.n == combined.n
    for c in CATEGORIES:
        assert merged.totals[c] == pytest.approx(combined.totals[c])
        if combined.stats[c].count:
            assert merged.stats[c].quantile(0.5) == pytest.approx(
                combined.stats[c].quantile(0.5))
    flat = merged.flat()
    assert flat["blame_top"] == "compute" and flat["blame_n"] == 20
    assert set(f"blame_{c}_ms" for c in CATEGORIES) <= set(flat)


# -- export -------------------------------------------------------------------

def test_chrome_trace_export_schema(tmp_path):
    """The exported payload is valid Chrome trace-event JSON: complete
    spans (ph=X with numeric us ts/dur), process/thread metadata, and
    instants with a scope — loadable in Perfetto."""
    wrt = _shape_run("rag", "atomic+batch", faults=True)
    path = tmp_path / "trace.json"
    payload = wrt.tracer.export_chrome_trace(str(path))
    reloaded = json.loads(path.read_text())
    assert reloaded == json.loads(json.dumps(payload))
    events = reloaded["traceEvents"]
    assert reloaded["displayTimeUnit"] == "ms"
    phs = {e["ph"] for e in events}
    assert phs <= {"X", "M", "i"} and {"X", "M", "i"} <= phs
    for e in events:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) and e["ts"] >= 0.0
            assert isinstance(e["dur"], float) and e["dur"] > 0.0
            assert e["cat"] in CATEGORIES
            assert e["args"]["instance"]
        elif e["ph"] == "M":
            assert e["name"] == "process_name" and e["args"]["name"]
        else:
            assert e["s"] in ("t", "g")
    # one process per node plus the synthetic cluster track
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert "cluster" in names and len(names) >= 2


# -- overhead envelope --------------------------------------------------------

def _microbench_runtime(n_tasks):
    store = CascadeStore([f"n{i}" for i in range(8)])
    store.create_object_pool("/x", store.nodes, 8,
                             affinity_set_regex=r"/[a-z0-9]+_")
    rt = Runtime(store)

    def task(ctx, key, value):
        yield Compute("gpu", 0.001)
        yield Put(key + "o", size=64, fire=False)
    rt.register("/x", task)
    for i in range(n_tasks):
        rt.client_put(i * 1e-4, f"/x/g{i % 64}_{i}", size=16)
    return rt


def _microbench_wall(traced, n_tasks=12_500):
    """One 50k-event run; traced mode attributes EVERY task (sample
    rate 1) and the timed region pays the full run lifecycle: raw op
    records on the hot path, then completion + retention for all 64
    instance traces.  Categorization is pay-per-query by design
    (``TraceRecorder.materialize`` runs when a retained trace is first
    read), so it's exercised — and its output asserted — outside the
    timed region, the way a post-run blame query would.

    The collector is off inside the timed region for BOTH variants: a
    collection pass landing in one variant and not the other measures
    generational phase alignment (and whatever heap the host process —
    e.g. pytest — retains), not the tracing code.  Tracing's own GC
    pressure is guarded separately: the returned tracked-object count
    asserts the raw record design (flat lists of atoms, no per-op
    containers) leaves the collector's workload untouched."""
    rt = _microbench_runtime(n_tasks)
    if traced:
        rec = TraceRecorder().attach(rt.sim)
        for g in range(64):
            rec.begin(f"g{g}", 0.0)
        rt.trace_of = lambda key: rec.live.get(instance_of(key))
    gc.collect()
    gc.disable()
    try:
        t0 = time.process_time()
        rt.run()
        if traced:              # pay completion + retention too
            for tr in list(rec.live.values()):
                rec.complete(tr, rt.sim.now)
        wall = time.process_time() - t0
        tracked = len(gc.get_objects())     # pre-materialization census
    finally:
        gc.enable()
    assert rt.sim.events_fired == 50_000    # tracing adds ZERO events
    assert rt.sim.completed_tasks == n_tasks
    if traced:
        assert rec.n_completed == 64
        retained = rec.traces()             # materializes deferred records
        assert len(retained) == 64
        assert rec.n_spans >= n_tasks       # every compute op attributed
        assert sum(len(tr.spans) for tr in retained) == rec.n_spans
    return wall, tracked


def test_tracing_overhead_within_10pct_on_50k_events():
    """The tier-1 overhead guard: tracing on the 50k-event DES
    microbench stays within 10% of the untraced CPU time, and adds a
    bounded number of GC-tracked objects (50k raw op records must not
    grow the collector's workload — the flat-atom record design).
    Interleaved off/on pairs (host speed drifts over seconds —
    back-to-back blocks bias the comparison), min-of-5 each (under
    several test workers a single slow run is common), and a small
    absolute floor for timer noise on short runs."""
    offs, ons = [], []
    for _ in range(5):
        offs.append(_microbench_wall(False))
        ons.append(_microbench_wall(True))
    off, on = min(w for w, _ in offs), min(w for w, _ in ons)
    assert on <= off * 1.10 + 0.05, (on, off)
    # tracked-object census: 12.5k recorded ops may cost a few hundred
    # bookkeeping containers (traces, their lists), never one per op
    tracked_off, tracked_on = offs[-1][1], ons[-1][1]
    assert tracked_on - tracked_off < 3_000, (tracked_on, tracked_off)


# -- property: exactness over random graphs (slow job) ------------------------

@pytest.mark.slow
def test_blame_exactness_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    def chain_workflow(chain, n_shards):
        g = WorkflowGraph("prop")
        g.add_tier("t", n_shards, dict(RES))
        for i in range(len(chain) + 1):
            g.add_pool(f"/p{i}", tier="t", shards=n_shards)
        for i, (fanout, join, cost) in enumerate(chain):
            g.add_stage(f"s{i}", pool=f"/p{i}", resource="gpu",
                        cost=cost * 1e-3,
                        emits=[Emit(f"/p{i + 1}", fanout=fanout, size=64)],
                        join=join and i > 0, sink=(i == len(chain) - 1))
        return g.validate()

    CHAINS = st.lists(
        st.tuples(st.integers(min_value=1, max_value=3),
                  st.booleans(),
                  st.integers(min_value=0, max_value=20)),
        min_size=1, max_size=4)

    @given(CHAINS,
           st.integers(min_value=1, max_value=6),            # shards
           st.integers(min_value=1, max_value=12),           # instances
           st.sampled_from(MODES),
           st.booleans())                                    # faults
    @settings(max_examples=25, deadline=None)
    def prop(chain, n_shards, n_instances, mode, faults):
        g = chain_workflow(chain, n_shards)
        wrt = WorkflowRuntime(g, tracing=True, **mode_kwargs(mode))
        if faults:
            inj = wrt.enable_faults()
            inj.fail_node(sorted(wrt.rt.nodes)[0], at=0.02, duration=0.05)
        for i in range(n_instances):
            wrt.submit(f"req{i}", at=0.01 + i * 1e-3)
        wrt.run()
        assert wrt.tracer.n_completed == n_instances
        for tr in wrt.tracer.traces():
            parts = decompose(tr)
            assert abs(sum(parts.values()) - tr.e2e) < 1e-6
            segs = critical_path(tr)
            assert segs[0][2] == tr.t_submit
            assert segs[-1][3] == tr.t_complete

    prop()
