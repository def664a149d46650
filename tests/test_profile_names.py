"""The names a profile of training shows: the device scopes in the
compiled step's ``op_name`` metadata, and the host loop's step and
``loop.*`` spans in a profiler trace (docs/profiling.md)."""
import dataclasses
import glob
import re

import jax
import pytest

import repro.configs as C
from repro import api
from repro.configs.base import (AmbdgConfig, DelayConfig, MeshConfig,
                                RunConfig, TRAIN_4K)
from repro.models import build_model
from repro.train import profile
from repro.train.loop import LoopConfig, train

MODEL_SCOPES = ("model.embed", "model.mixer", "model.mlp", "model.head")


def _rc(arch, **kw):
    cfg = C.get_smoke_config(arch)
    rc = RunConfig(model=cfg,
                   shape=dataclasses.replace(TRAIN_4K, seq_len=32,
                                             global_batch=4),
                   mesh=MeshConfig(n_pods=1, data=1, model=1),
                   ambdg=AmbdgConfig(tau=1, n_microbatches=2, b_bar=4.0),
                   **kw)
    return build_model(cfg), rc


# (arch, delay process): a dense decoder and a hybrid on the fixed
# tau=1 ring, a dense decoder on the delay-tolerant (variable) ring
CASES = {
    "dense": ("qwen1.5-0.5b", DelayConfig()),
    "hybrid": ("zamba2-2.7b", DelayConfig()),
    "variable_ring": ("qwen1.5-0.5b",
                      DelayConfig(process="heavy_tail", tau_max=2,
                                  delay_min=1, seed=3)),
    "moe": ("moonlight-16b-a3b", DelayConfig()),
}
MOE_SCOPES = ("model.attn", "model.moe", "moe.route", "moe.dispatch",
              "moe.experts", "moe.shared", "moe.combine")


def _op_names(case):
    arch, delay = CASES[case]
    model, rc = _rc(arch, delay=delay)
    strategy = api.build(model, rc)
    state = jax.eval_shape(strategy.init_state, jax.random.PRNGKey(0))
    batch = model.dummy_batch(4, 32)
    if delay.process != "fixed":
        batch["delay"] = jax.numpy.int32(1)
    text = (jax.jit(strategy.train_step).lower(state, batch)
            .compile().as_text())
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("case", sorted(CASES))
def test_step_carries_every_scope(case):
    names = _op_names(case)
    joined = "\n".join(names)
    for scope in MODEL_SCOPES + ("master.stage", "master.update",
                                 "master.norm", "worker.accumulate"):
        assert scope in joined, (case, scope)
    # the fixed f32 ring's pop is a static slot read that XLA folds into
    # its consumer; the variable ring's masked fold is work of its own
    assert ("master.ring" in joined) == (case == "variable_ring"), case
    # one scope tells the three passes apart: forward under
    # jvp(worker.forward), backward under its transpose, recomputation
    # of the checkpointed blocks under rematted_computation
    by_pass = {p: [n for n in names if p in profile.parts(n)]
               for p in ("forward", "backward", "recompute")}
    assert all(n.count("jvp(worker.forward)") and "transpose(" not in n
               for n in by_pass["forward"]), case
    assert all("rematted_computation" in n for n in by_pass["recompute"])
    for p, ops in by_pass.items():
        assert ops, (case, p)
        # the mixer runs in all three passes
        assert any("model.mixer" in profile.parts(n) for n in ops), (case, p)
    # the master runs outside the differentiated loss
    assert not any("master." in n and "worker.forward" in n
                   for n in names), case
    assert any("master" in profile.parts(n) for n in names), case


def test_moe_step_carries_its_scopes():
    """Moonlight's step: the latent attention's projections under
    ``model.attn`` beside its core under ``model.mixer``, and the
    held-expert layer's five parts under ``model.moe``, each summed by
    ``profile.parts`` in the three passes."""
    names = _op_names("moe")
    joined = "\n".join(names)
    for scope in MOE_SCOPES:
        assert scope in joined, scope
    for p in ("forward", "backward", "recompute"):
        found = set()
        for n in names:
            got = profile.parts(n)
            if p in got:
                found.update(got)
        assert {"model.attn", "model.mixer", "model.moe", "moe.experts",
                "moe.route", "moe.shared"} <= found, (p, found)


def test_train_writes_step_and_loop_spans(tmp_path):
    from jax.profiler import ProfileData
    arch, delay = CASES["variable_ring"]
    model, rc = _rc(arch, delay=delay)
    loop = LoopConfig(n_steps=3, n_workers=2, samples_per_worker=2,
                      log_every=3)
    train(model, rc, loop)          # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are TraceMes, not frames
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        train(model, rc, loop)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    data = ProfileData.from_file(path[-1])
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "train" or e.name.startswith("loop.")]
    steps = [ev for ev in events if ev[0] == "train"]
    assert [ev[3]["step_num"] for ev in steps] == [0, 1, 2]
    for name, s, t, stats in events:
        if name == "train":
            continue
        # every loop span lies inside one step span
        assert any(s0 <= s and t <= t0 for _, s0, t0, _ in steps), name
    names = [ev[0] for ev in events]
    for name in ("loop.batch", "loop.draws", "loop.put", "loop.dispatch"):
        assert names.count(name) == 3, name
    assert names.count("loop.log") == 1
    # the variable ring's draw carries the step's delay
    assert all("delay" in ev[3] for ev in events if ev[0] == "loop.draws")
    # a model without the dropless MoE layer names no held rows
    assert not [e for plane in data.planes for line in plane.lines
                for e in line.events if e.name.startswith("moe.")]


def test_train_names_the_held_rows_of_each_step(tmp_path):
    """Moonlight's loop names each step's held-expert rows in a span
    one step late, inside the next step's span: two spans for three
    steps, each the step's ``moe_held_rows``."""
    from jax.profiler import ProfileData
    arch, delay = CASES["moe"]
    model, rc = _rc(arch, delay=delay)
    loop = LoopConfig(n_steps=3, n_workers=2, samples_per_worker=2,
                      log_every=1)
    train(model, rc, loop)          # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = train(model, rc, loop)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    data = ProfileData.from_file(path[-1])
    spans = sorted((e.start_ns, e.name) for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("moe.held_rows="))
    rows = [m["moe_held_rows"] for m in out["history"]]
    assert [n for _, n in spans] == [f"moe.held_rows={r:.0f}"
                                     for r in rows[:2]]
    assert all(r > 0 for r in rows)


# one step of a made-up trace: a step module of 100 us; a forward mixer
# op (op_name inline), the dual-update kernel (op_name interned as a
# reference, as the TPU writes it) and a copy with no op_name; two
# overlapping host-prep spans and a dispatch
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8] fusion()"
    stats { metadata_id: 1
            str_value: "jit(train_step)/jvp(worker.forward)/model.mixer/dot" } } }
  event_metadata { key: 2 value { id: 2
    name: "%dual_update_fused.1 = f32[8] custom-call()"
    stats { metadata_id: 1 ref_value: 5 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8] copy()" } }
  event_metadata { key: 9 value { id: 9 name: "jit_train_step(1)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 5 value { id: 5
    name: "jit(train_step)/master.update/dual_update_fused/pallas_call" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "loop.batch" } }
  event_metadata { key: 2 value { id: 2 name: "loop.put" } }
  event_metadata { key: 3 value { id: 3 name: "loop.dispatch" } }
}
"""


def test_profile_sums_a_trace_by_name(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "one.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    out = profile.summarize(*profile.load(profile.find_xplane(
        str(tmp_path))))
    assert out["steps"] == 1 and out["step_ms"] == pytest.approx(0.1)
    assert out["device_ms"] == pytest.approx({
        "forward": 0.01, "model.mixer": 0.01, "master": 0.02,
        "master.update": 0.02, "unscoped": 0.005})
    # host_prep is the union of the overlapping batch and put spans
    assert out["host_ms"] == pytest.approx({
        "loop.batch": 0.002, "loop.put": 0.002, "loop.dispatch": 0.004,
        "host_prep": 0.003})
