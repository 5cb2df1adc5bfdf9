"""The serve step's scope table (``scopes``), on a synthetic device trace
and on a serve step compiled and traced on the CPU."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from benchmarks.chip import batch, harness, scopes
from conftest import BATCH_CELL, tiny_cell

US = 1_000_000                      # picoseconds in a microsecond
SERVE = "jit(serve_step)"

# Each serve-step execution, in microseconds from its start: a layer scan
# (``while``) enclosing four ops, then a copy that XLA inserted (no
# op_name) and the head.
STEP_OPS = [
    ("%while.1 = (s32[]) while(%t)", 10, 60),
    ("%fusion.3 = bf16[16,4096]{1,0} fusion(%p)", 12, 30),
    ("%bitcast_dynamic-update-slice_fusion.2 = bf16[2,16]{1,0} fusion(%q)",
     30, 40),
    ("%fusion.4 = bf16[2,16]{1,0} fusion(%r)", 40, 45),
    ("%decode_attention.1 = bf16[16,32]{1,0} custom-call(%s)", 45, 55),
    ("%copy.5 = bf16[2,16]{1,0} copy(%x)", 60, 80),
    ("%convert_reduce_fusion = (bf16[16]) fusion(%a, %b)", 80, 95),
]
STEPS_AT = (0, 200)                 # two executions of 100 us each
PREFILL = ("jit_prefill(3)", 400, 450, "%fusion.9 = bf16[4]{0} fusion(%y)")

HLO = f"""HloModule jit_serve_step, entry_computation_layout={{()->()}}

%body (p: (s32[])) -> (s32[]) {{
  %fusion.3 = bf16[16,4096]{{1,0}} fusion(%p), kind=kOutput, calls=%c1, metadata={{op_name="{SERVE}/layers/while/body/attn/qkv/dot_general" stack_frame_id=3}}
  %bitcast_dynamic-update-slice_fusion.2 = bf16[2,16]{{1,0}} fusion(%q), kind=kLoop, calls=%c2, metadata={{op_name="{SERVE}/layers/while/body/dynamic_update_slice"}}
  %fusion.4 = bf16[2,16]{{1,0}} fusion(%r), kind=kLoop, calls=%c3, metadata={{op_name="{SERVE}/layers/while/body/attn/kv_write/scatter"}}
  ROOT %decode_attention.1 = bf16[16,32]{{1,0}} custom-call(%s), custom_call_target="tpu_custom_call", metadata={{op_name="{SERVE}/layers/while/body/attn/attn_kernel/pallas_call"}}
}}

ENTRY %main.1 () -> () {{
  %while.1 = (s32[]) while(%t), condition=%cond, body=%body, metadata={{op_name="{SERVE}/layers/while"}}
  %copy.5 = bf16[2,16]{{1,0}} copy(%x)
  ROOT %convert_reduce_fusion = (bf16[16]) fusion(%a, %b), kind=kLoop, calls=%c4, metadata={{op_name="{SERVE}/unembed/...d,dv->...v/dot_general"}}
}}
"""

# self time of each row in one step, microseconds: the ``while`` keeps the
# 7 us its four ops leave uncovered
ROWS_US = {"layers": 10 + 7, "qkv": 18, "kv_write": 5, "attn_kernel": 10,
           "unscoped": 20, "unembed": 15}


def _xspace() -> bytes:
    meta, mods, ops = {}, [], []

    def mid(name):
        return meta.setdefault(name, len(meta) + 1)

    def event(name, s, e):
        return (f"events {{ metadata_id: {mid(name)} offset_ps: {s * US} "
                f"duration_ps: {(e - s) * US} }}")

    for at in STEPS_AT:
        mods.append(event("jit_serve_step(7)", at, at + 100))
        ops += [event(n, at + s, at + e) for n, s, e in STEP_OPS]
    name, s, e, op = PREFILL
    mods.append(event(name, s, e))
    ops.append(event(op, s + 5, e - 5))
    text = "planes { id: 1 name: \"/device:TPU:0\"\n"
    text += ("lines { id: 1 name: \"XLA Modules\" timestamp_ns: 0 "
             + " ".join(mods) + " }\n")
    text += ("lines { id: 2 name: \"XLA Ops\" timestamp_ns: 0 "
             + " ".join(ops) + " }\n")
    for name, i in meta.items():
        text += (f"event_metadata {{ key: {i} value {{ id: {i} "
                 f"name: {json.dumps(name)} }} }}\n")
    return ProfileData.text_proto_to_serialized_xspace(text + "}")


@pytest.fixture(scope="module")
def table():
    pd = ProfileData.from_serialized_xspace(_xspace())
    return scopes.table(*scopes.serve_events(pd), scopes.op_names(HLO))


def test_ops_count_self_time_per_execution(table):
    assert table.executions == 2
    for row, us in ROWS_US.items():
        assert table.ms_per_step([row]) == pytest.approx(us * 1e-3)


def test_each_op_lands_in_one_row_and_rows_add_up(table):
    assert set(table.rows) == set(ROWS_US)
    assert sum(table.ops.values()) == pytest.approx(sum(table.rows.values()))
    assert {op for _, op in table.ops} == {
        "while", "fusion", "bitcast_dynamic-update-slice_fusion",
        "decode_attention", "copy", "convert_reduce_fusion"}
    # the rows are the serve step's device time: every op's interval,
    # counted once, and nothing of the prefill module
    busy = 2 * (95 - 10) * 1e-6
    assert sum(table.rows.values()) == pytest.approx(busy)
    assert table.module_s == pytest.approx(2 * 100e-6)


def test_no_reading_without_scopes_or_with_another_program():
    pd = ProfileData.from_serialized_xspace(_xspace())
    mods, ops = scopes.serve_events(pd)
    unscoped = scopes.op_names(HLO.replace("op_name", "source_file"))
    assert set(unscoped.values()) == {""}
    assert scopes.table(mods, ops, unscoped) is None      # parent program
    other = scopes.op_names(HLO.replace("%fusion.4 ", "%fusion.44 "))
    assert scopes.table(mods, ops, other) is None         # not the same text


def test_row_is_the_innermost_program_scope():
    assert scopes.row(f"{SERVE}/layers/while/body/attn/qkv/dot") == "qkv"
    assert scopes.row(f"{SERVE}/layers/while/body/squeeze") == "layers"
    assert scopes.row(f"{SERVE}/sample/argmax") == "sample"
    assert scopes.row("jit(f)/while/body/dynamic_slice") == "unscoped"
    assert scopes.row("") == "unscoped"


def test_metrics_read_the_table_once(tmp_path, monkeypatch):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    texts = []
    monkeypatch.setattr(scopes, "latest_trace", lambda: str(path))
    monkeypatch.setattr(scopes, "serve_step_text",
                        lambda run: texts.append(run) or HLO)
    run = types.SimpleNamespace(trace=object())
    read = {n: harness.load_reader(n)(run) for n in
            ("serve_cache_ms", "serve_weights_ms", "serve_unscoped_ms")}
    assert read == pytest.approx({"serve_cache_ms": 0.022,
                                  "serve_weights_ms": 0.033,
                                  "serve_unscoped_ms": 0.020})
    assert len(texts) == 1
    assert harness.load_reader("serve_cache_ms")(
        types.SimpleNamespace(trace=None)) is None         # untraced run


def test_instruction_names_match_a_cpu_compiled_serve_step(tmp_path):
    """The fallback on a real program: the serve step compiled again from
    the cell's shapes names every instruction that its traced run on the
    CPU executed, and the ops land in the program's scopes."""
    cell = tiny_cell(BATCH_CELL)
    cfg, mix = cell["config"], cell["traffic"]
    B, P, G = mix["batch"], mix["prompt"], mix["answer"]
    prog = batch.build(cfg, mix, 1, jax.devices()[:1])
    run = types.SimpleNamespace(cfg=cfg, served=[
        dict(batch=0, prompt=[0] * P, gen=G)] * B)
    names = scopes.op_names(scopes.serve_step_text(run))
    jax.profiler.start_trace(str(tmp_path))
    toks = batch.dispatch(prog, jnp.zeros((B, P), jnp.int32), steps=2)
    np.asarray(toks)
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(harness.trace_mod.latest_xplane(str(tmp_path)))
    ran = {dict(e.stats)["hlo_op"] for p in pd.planes for ln in p.lines
           for e in ln.events
           if str(dict(e.stats).get("hlo_module", "")).startswith(
               scopes.SERVE_MODULE)}
    assert ran and ran <= set(names)
    rows = {scopes.row(names[i]) for i in ran}
    assert {"layers", "qkv", "kv_write", "attn_kernel", "attn_out", "mlp",
            "unembed"} <= rows
