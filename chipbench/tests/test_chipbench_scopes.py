"""Attribution of the compiled step to the program's named layers
(chipbench/scopes.py): the op_name rule on its cases, the parser on a
short HLO text, the tiny cells' steps compiled on the CPU, and the
report tool's windows with the loader's counters and spans."""
import json
import os
import subprocess
import sys

import jax
import pytest

import chipbench_testlib as lib
from chipbench import bench, scopes, weights
from repro.obs import scopes as names

T = "jit(train_step)/"
BODY = "while/body/closed_call/"


@pytest.mark.parametrize("op_name,want", [
    (T + "jvp(layers)/" + BODY + "mlp/dot_general", ("mlp", "fwd")),
    (T + "transpose(jvp(layers))/" + BODY + "checkpoint/mlp/dot_general",
     ("mlp", "bwd")),
    (T + "transpose(jvp(layers))/" + BODY + "checkpoint/"
     "rematted_computation/attention/attention_core/attention_core/"
     "jit(flash_attention)/flash_attention/pallas_call",
     ("attention_core", "remat")),
    # a custom_vjp backward rule's own forward of its reference
    (T + "transpose(jvp(layers))/" + BODY + "checkpoint/attention/"
     "attention_core/attention_core/jit(flash_attention)/jvp()/exp",
     ("attention_core", "bwd")),
    (T + "transpose(jvp(layers))/" + BODY + "checkpoint/attention/"
     "attention_core/attention_core/jit(flash_attention)/"
     "transpose(jvp(bqhd,bkhd->bhqk))/dot_general",
     ("attention_core", "bwd")),
    (T + "jvp(layers)/" + BODY + "attention/rope/mul", ("attention", "fwd")),
    (T + "transpose(jvp(loss_head))/dot_general", ("loss_head", "bwd")),
    (T + "optimizer/jit(clip)/max", ("optimizer", "fwd")),
    (T + "jvp()/shard_map/" + BODY + "pipe_tick/pipe_send/ppermute",
     ("pipe_send", "fwd")),
    (T + "transpose(jvp())/shard_map/" + BODY + "pipe_tick/add_any",
     ("pipe_tick", "bwd")),
    (T + "jvp()/rsqrt", (None, "fwd")),
    (T + "transpose(jvp())/mul", (None, "bwd")),
    ("jit(f)/jvp(bqhd,bkhd->bhqk)/dot_general", (None, "fwd")),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


FLASH = (T + "jvp(layers)/" + BODY + "attention/attention_core/"
         "attention_core/jit(flash_attention)/flash_attention/pallas_call")
HLO = f'''HloModule jit_train_step, is_scheduled=true

FileNames
1 "model.py"

FunctionNames
1 "apply_mlp"

FileLocations
1 {{file_name_id=1 function_name_id=1 line=70 end_line=70 column=8}}

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}


%fused_computation.7 (param_0: bf16[8,64], param_1: bf16[64,256]) -> bf16[8,256] {{
  %param_0 = bf16[8,64]{{1,0}} parameter(0)
  %param_1 = bf16[64,256]{{1,0}} parameter(1)
  ROOT %convolution.3 = bf16[8,256]{{1,0}} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={{op_name="{T}transpose(jvp(layers))/{BODY}checkpoint/mlp/dot_general" stack_frame_id=1}}
}}

ENTRY %main.90 (p0: bf16[8,64], p1: bf16[64,256], p2: f32[8,256]) -> f32[8,256] {{
  %p0 = bf16[8,64]{{1,0}} parameter(0), metadata={{op_name="state[0]"}}
  %p1 = bf16[64,256]{{1,0}} parameter(1)
  %p2 = f32[8,256]{{1,0}} parameter(2)
  %fusion.532 = bf16[8,256]{{1,0}} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.7
  %flash_attention.17 = bf16[8,256]{{1,0}} custom-call(%fusion.532), custom_call_target="tpu_custom_call", metadata={{op_name="{FLASH}" stack_frame_id=1}}
  %dot.100 = f32[8,256]{{1,0}} dot(%flash_attention.17, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  %multiply.5 = f32[8,256]{{1,0}} multiply(%p2, %p2), metadata={{op_name="{T}optimizer/mul"}}
  %add.3 = f32[8,256]{{1,0}} add(%multiply.5, %dot.100), metadata={{op_name="{T}transpose(jvp())/add_any"}}
  ROOT %exp.1 = f32[8,256]{{1,0}} exponential(%add.3), metadata={{op_name="{T}transpose(jvp(layers))/{BODY}checkpoint/attention/attention_core/attention_core/jit(flash_attention)/jvp()/exp"}}
}}
'''


@pytest.mark.parametrize("inst,want", [
    ("fusion.532", ("mlp", "bwd")),          # its computation's root
    ("convolution.3", ("mlp", "bwd")),
    ("flash_attention.17", ("attention_core", "fwd")),
    ("dot.100", ("attention_core", "fwd")),  # its first operand's
    ("multiply.5", ("optimizer", "fwd")),
    ("add.3", (None, "bwd")),
    ("exp.1", ("attention_core", "bwd")),
    ("p0", (None, "fwd")),
    ("p1", (None, "fwd")),
])
def test_op_scopes_of_a_module(inst, want):
    assert tuple(scopes.op_scopes(HLO)[inst][1:]) == want


def test_parse_and_strip():
    ins = scopes.parse_hlo(HLO)
    assert sorted(ins) == sorted([
        "param_0", "param_1", "convolution.3", "p0", "p1", "p2",
        "fusion.532", "flash_attention.17", "dot.100", "multiply.5",
        "add.3", "exp.1"])
    assert ins["fusion.532"]["calls"] == "fused_computation.7"
    assert ins["fusion.532"]["computation"] == "main.90"
    assert ins["dot.100"]["operands"] == ["flash_attention.17", "p1"]
    assert ins["exp.1"]["root"] and ins["exp.1"]["opcode"] == "exponential"
    assert scopes.op_scopes(HLO)["flash_attention.17"][0] == FLASH
    bare = scopes.strip_metadata(HLO)
    assert "metadata" not in bare and "FileNames" not in bare
    assert "stack_frame" not in bare and '"model.py"' not in bare
    assert ("  %fusion.532 = bf16[8,256]{1,0} fusion(%p0, %p1), "
            "kind=kOutput, calls=%fused_computation.7\n") in bare
    assert bare.count(" = ") == HLO.count(" = ")
    assert scopes.strip_metadata(bare) == bare


# -- the tiny cells' compiled steps --------------------------------------

DENSE = {names.EMBED, names.LAYERS, names.ATTENTION, names.ATTENTION_CORE,
         names.MLP, names.LOSS_HEAD, names.OPTIMIZER}
MAMBA = {names.EMBED, names.LAYERS, names.SSD, names.SSD_CORE,
         names.LOSS_HEAD, names.OPTIMIZER}
PIPE = DENSE | {names.PIPE_TICK, names.PIPE_SEND}
# each core's Pallas kernel, whose body (interpret mode on the CPU) lies
# under jit(<kernel>)/<kernel>/ and its custom_vjp rule under
# jit(<kernel>)/transpose(jvp())/
KERNEL = {names.ATTENTION_CORE: "flash_attention", names.SSD_CORE: "ssd_scan"}
HEAVY = ("dot", "convolution", "custom-call")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_root(str(tmp_path_factory.mktemp("bench")))


def _compiled(root, cell, backend):
    name = f"{cell}-{backend}"
    with open(os.path.join(root, "workloads", cell + ".json"),
              encoding="utf-8") as f:
        w = json.load(f)
    with open(os.path.join(root, "workloads", name + ".json"), "w",
              encoding="utf-8") as f:
        json.dump(dict(w, backend=backend), f)
    b = bench.Bench(name, jax.devices()[:1], root)
    b.mode.compile(b.mode.init(weights.seed_key(0)))
    return b.mode.compiled.as_text()


@pytest.fixture(scope="module")
def pipeline_text(tmp_path_factory):
    here = os.path.dirname(os.path.abspath(__file__))
    out = str(tmp_path_factory.mktemp("pipe") / "step.hlo")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [here, lib.REPO, os.path.join(lib.REPO, "src")]))
    p = subprocess.run([sys.executable,
                        os.path.join(here, "scopes_pipeline_main.py"), out],
                       capture_output=True, text=True, env=env, cwd=lib.REPO,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    with open(out, encoding="utf-8") as f:
        return f.read()


def _heavy(text):
    """Instructions that do matmul work: dots, convolutions, custom
    calls, and fusions whose computation holds one."""
    ins = scopes.parse_hlo(text)
    comps = {i["computation"] for i in ins.values() if i["opcode"] in HEAVY}
    return [n for n, i in ins.items()
            if i["opcode"] in HEAVY or i["calls"] in comps]


@pytest.mark.parametrize("cell,backend,want", [
    ("tiny_dense.t", "einsum", DENSE), ("tiny_dense.t", "pallas", DENSE),
    ("tiny_mamba.t", "einsum", MAMBA), ("tiny_mamba.t", "pallas", MAMBA),
    ("tiny_dense2.pipe", "auto", PIPE)])
def test_compiled_step_is_attributed(root, pipeline_text, cell, backend,
                                     want):
    text = pipeline_text if cell.endswith("pipe") else \
        _compiled(root, cell, backend)
    op = scopes.op_scopes(text)
    got = {s for _, s, _ in op.values() if s}
    assert got == want
    # every layer's backward lands under it
    assert {s for _, s, p in op.values() if s and p == "bwd"} >= \
        want - {names.OPTIMIZER}
    heavy = _heavy(text)
    assert heavy and all(op[n][1] for n in heavy), \
        [(n, op[n][0]) for n in heavy if not op[n][1]]
    for core, name in KERNEL.items():
        if core not in want or backend != "pallas":
            continue
        # the kernel runs forward and again for the backward pass; its
        # custom_vjp rule (the jnp reference's VJP) is the backward
        kernel = {(s, p) for o, s, p in op.values()
                  if o and f"jit({name})/{name}/" in o}
        assert kernel == {(core, "fwd"), (core, "remat")}
        rule = {(s, p) for o, s, p in op.values()
                if o and f"jit({name})/transpose(jvp())/" in o}
        assert rule == {(core, "bwd")}


# -- the report tool's windows on the CPU --------------------------------

def test_report_counts_the_loader(root):
    from chipbench import scope_report
    b = bench.Bench("tiny_dense.t", jax.devices()[:1], root)
    state, loader, _ = b.start(2**31 + 5)
    out = scope_report.report(b, state, loader, 0.2,
                              b.mode.compiled.as_text())
    loader.close()
    n = out["steps"]["traced"]
    assert n == b.workload["trace_steps"] and out["steps"]["untraced"] >= 1
    c = out["loader"]["counters"]
    assert c["batches"] == n and c["queue_wait_s"] >= 0 and c["put_s"] > 0
    assert out["layer_ms"]["data_queue_wait_ms"] == pytest.approx(
        1e3 * c["queue_wait_s"] / n)
    # the consumer's spans of the traced steps, inside the window
    spans = out["loader"]["span_ms"]
    assert {names.DATA_QUEUE_WAIT, names.DATA_DEVICE_PUT} <= set(spans)
    assert out["loader"]["spans_in_window"] >= 2 * n
    assert spans[names.DATA_QUEUE_WAIT] == pytest.approx(
        out["layer_ms"]["data_queue_wait_ms"], rel=0.5, abs=0.5)


# -- a trace recorded on one TPU v5e chip ---------------------------------
# chipbench/testdata/record_scoped_trace.py: two steps of the gradient of
# a matmul under ``mlp`` and a sum of squares under ``loss_head``, fed by
# the program's loader.  The compiler fused the sum of squares into the
# matmuls' fusions, whose op_names are mlp's: per step
# convolution_tanh_fusion (forward) 17,632 and 17,692 ns, fusion
# (backward) 12,792 and 12,797 ns, four copies of 21 and 20 ns.

TESTDATA = os.path.join(lib.BENCH_DIR, "testdata")


@pytest.fixture(scope="module")
def recorded():
    from chipbench import tracing
    path = os.path.join(TESTDATA, "scoped.xplane.pb")
    with open(os.path.join(TESTDATA, "scoped.hlo.txt"),
              encoding="utf-8") as f:
        op = scopes.op_scopes(f.read())
    return tracing.reduce_xplane(path), op, scopes.program_spans(path)


def test_recorded_trace_by_scope(recorded):
    trace, op, _ = recorded
    assert op["convolution_tanh_fusion"][1:] == ["mlp", "fwd"]
    assert op["fusion"][1:] == ["mlp", "bwd"]
    ms = scopes.scope_ms(trace, op, 2)
    assert ms == pytest.approx({("mlp", "fwd"): 17_662e-6,
                                ("mlp", "bwd"): 12_794.5e-6,
                                (None, "fwd"): 20.5e-6})
    assert scopes.layer_ms(ms, "mlp") == pytest.approx(30_456.5e-6)
    assert scopes.layer_ms(ms, "loss_head") is None
    cov = scopes.coverage(trace, op)
    assert cov["mapped"] == 1.0
    assert cov["scoped"] == pytest.approx(60_913 / 60_954)


def test_recorded_program_spans(recorded):
    from chipbench import tracing
    trace, _, spans = recorded
    # the harness's reduction keeps its own spans only
    assert [h[0] for h in trace["host"]] == [
        "window", "data", "dispatch", "wait", "data", "dispatch", "wait"]
    assert [s[0] for s in spans] == [
        names.DATA_PRODUCE, names.DATA_QUEUE_WAIT, names.DATA_PRODUCE,
        names.DATA_DEVICE_PUT, names.DATA_PRODUCE, names.DATA_QUEUE_WAIT,
        names.DATA_DEVICE_PUT, names.DATA_PRODUCE]
    lo, hi = tracing.window(trace)
    assert all(lo <= s <= s + d <= hi for _, s, d in spans)
    # the consumer's spans nest in the harness's data spans, on one clock
    data = [h for h in trace["host"] if h[0] == "data"]
    mine = [s for s in spans if s[0] != names.DATA_PRODUCE]
    for i, (_, s, d) in enumerate(mine):
        _, a, b = data[i // 2]
        assert a <= s and s + d <= a + b


def test_recorded_device_clock_leads_the_host(recorded):
    """The device's ops are placed on the host's clock only roughly:
    each step's first op is recorded before the host dispatched it.
    The least shift that puts it after its dispatch span starts, and
    its last op before its wait span ends, bounds the offset, which
    bounds how far an idle gap's host label can be trusted."""
    trace, _, _ = recorded
    host = trace["host"]
    ops = trace["devices"]["0"]
    starts = [ops[0][1], ops[6][1]]
    ends = [ops[5][1] + ops[5][2], ops[11][1] + ops[11][2]]
    dispatch = [h[1] for h in host if h[0] == "dispatch"]
    wait_end = [h[1] + h[2] for h in host if h[0] == "wait"]
    least = max(d - s for d, s in zip(dispatch, starts))
    most = min(w - e for w, e in zip(wait_end, ends))
    assert (least, most) == (359_572, 1_855_027)
