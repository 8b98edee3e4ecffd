"""chip_smoke.py off the chip: its step-building function at a tiny config on
the virtual CPU devices (dp=1 and dp=4: finite falling loss, dp parity, the
Pallas kernels dispatched per data-parallel shard), its HLO readers on a
recorded TPU program, and the script itself refusing to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as cs
from paddle_tpu.core import flags
from paddle_tpu.ops.pallas import config as pcfg
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.text.ernie import ErnieConfig

REPO = Path(__file__).resolve().parents[1]

# kernel-eligible at toy size: head_dim 64, seq % 128 == 0, per-shard rows a
# multiple of the fused-LN's 256-row block (2 x 128)
TINY = dict(vocab_size=256, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=256,
            max_position_embeddings=128)
SEQ, GLOBAL_BATCH = 128, 8

needs_devices = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs the virtual CPU mesh")


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    mesh_mod.set_mesh(None)


@pytest.fixture
def _gate_open(monkeypatch):
    """Open the Pallas dispatch gate on CPU CI: the kernels chip_smoke
    expects run in interpret mode, through the same dispatch sites."""
    monkeypatch.setattr(pcfg, "kernel_enabled",
                        lambda name: bool(flags.get_flag(name)))


def _kernel_calls():
    c = pcfg._m_calls
    return {k: c.value(kernel=k) for k in
            ("flash_attention_packed", "fused_rdln", "fused_layer_norm")}


def _build(cfg, dp, init=None):
    return cs.build_training(cfg, jax.devices(), dp,
                             cs.make_batch(cfg, GLOBAL_BATCH, SEQ),
                             init_params=init, compute_dtype=jnp.float32)


def _steps(t, n):
    losses = []
    for _ in range(n):
        t.params, t.opt_state, loss = t.step(t.params, t.opt_state, t.batch,
                                             t.key)
        losses.append(float(loss))
    return losses


@needs_devices
def test_step_builder_dp1_and_dp4_train_and_agree(_gate_open):
    cfg = ErnieConfig(**TINY, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    before = _kernel_calls()
    t1 = _build(cfg, 1)
    raw = jax.tree_util.tree_map(np.asarray, t1.params)
    l1 = _steps(t1, 3)
    assert all(now > was for now, was in zip(_kernel_calls().values(),
                                             before.values()))
    t4 = _build(cfg, 4, init=raw)
    l4 = _steps(t4, 3)
    for losses in (l1, l4):
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    # three optimizer steps agree: the per-shard kernels' gradients (and
    # shard_map's psum of the replicated LN weights' cotangents) are right
    np.testing.assert_allclose(l4, l1, rtol=2e-4)
    # Leg A's mesh is one device of the visible eight; on the dp=4 mesh
    # every device holds its quarter of the batch and a copy of the state
    assert t1.trainer.mesh.devices.size == 1
    res = cs.device_residency(t4, jax.devices()[:4])
    assert res["batch_rows_per_device"] == GLOBAL_BATCH // 4
    with pytest.raises(cs.SmokeFailure, match="not sharded evenly"):
        cs.device_residency(t4, jax.devices()[:2])


@needs_devices
def test_dp4_dispatches_kernels_per_shard(_gate_open, monkeypatch):
    """Inside the dp=4 step every Pallas call sees the PER-SHARD batch (the
    shard_map at the dispatch sites), and dropout seeds differ per shard."""
    from paddle_tpu.ops.pallas import flash_attention_packed as fap
    from paddle_tpu.ops.pallas import layer_norm as fln

    seen = {"flash": [], "rdln": [], "seeds": []}
    orig_f, orig_r = fap.flash_attention_packed, \
        fln.fused_residual_dropout_layer_norm

    def spy_f(q, *a, **k):
        seen["flash"].append(q.shape)
        seen["seeds"].append(k["seed"].shape)
        return orig_f(q, *a, **k)

    def spy_r(x, *a, **k):
        seen["rdln"].append(x.shape)
        return orig_r(x, *a, **k)

    monkeypatch.setattr(fap, "flash_attention_packed", spy_f)
    monkeypatch.setattr(fln, "fused_residual_dropout_layer_norm", spy_r)
    losses = _steps(_build(ErnieConfig(**TINY), 4), 2)  # dropout on
    assert all(np.isfinite(losses))
    local = GLOBAL_BATCH // 4
    assert seen["flash"] and set(seen["flash"]) == {(local, SEQ, 128)}
    assert seen["rdln"] and set(seen["rdln"]) == {(local, SEQ, 128)}
    assert set(seen["seeds"]) == {(1,)}  # one seed per shard, sliced by dp


@needs_devices
def test_kernel_dispatch_beyond_dp(_gate_open):
    """A Mosaic call cannot be left to GSPMD on ANY multi-device mesh: with
    a tp axis the kernels run under a shard_map that is manual over every
    axis (tp sees replicated operands); inside the pp pipeline's
    partial-manual shard_map they fall back to XLA, counted.  Both agree
    with the one-device run."""
    import paddle_tpu
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.text.pretrainer import HybridPretrainer

    cfg = ErnieConfig(**TINY, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    batch = cs.make_batch(cfg, GLOBAL_BATCH, SEQ)
    paddle_tpu.seed(0)
    one = mesh_mod.build_mesh(dp=1, devices=jax.devices()[:1])
    raw = jax.tree_util.tree_map(
        np.asarray, HybridPretrainer(cfg, mesh=one).init_params())

    def two_steps(mesh, **kw):
        tr = HybridPretrainer(cfg, mesh=mesh, **kw)
        opt = Adam(learning_rate=1e-3)
        params = tr.place_params(raw)
        state = opt.init(params)
        sh = tr.data_shardings()
        placed = {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
        step = jax.jit(tr.make_train_step(opt), donate_argnums=(0, 1))
        losses = []
        for _ in range(2):
            params, state, loss = step(params, state, placed,
                                       jax.random.PRNGKey(0))
            losses.append(float(loss))
        return losses

    fallbacks = lambda: pcfg._m_fallbacks.value(  # noqa: E731
        kernel="flash_attention_packed", reason="partial_manual_mesh")
    ref = two_steps(one)
    calls0, fb0 = _kernel_calls(), fallbacks()
    got = two_steps(mesh_mod.build_mesh(dp=2, tp=2,
                                        devices=jax.devices()[:4]))
    np.testing.assert_allclose(got, ref, rtol=2e-4)
    assert _kernel_calls()["flash_attention_packed"] > \
        calls0["flash_attention_packed"] and fallbacks() == fb0
    calls1 = _kernel_calls()
    got = two_steps(mesh_mod.build_mesh(dp=2, pp=2,
                                        devices=jax.devices()[:4]),
                    num_micro=2)
    np.testing.assert_allclose(got, ref, rtol=2e-4)
    assert fallbacks() > fb0
    assert _kernel_calls()["fused_rdln"] == calls1["fused_rdln"]


def test_mesh_scope_and_batch_shards():
    m4 = mesh_mod.build_mesh(dp=4, devices=jax.devices()[:4]) \
        if jax.device_count() >= 4 else None
    assert mesh_mod.batch_shards(8) == 1          # no mesh: one shard
    if m4 is None:
        return
    with mesh_mod.mesh_scope(m4):
        assert mesh_mod.batch_shards(8) == 4
        assert mesh_mod.batch_shards(6) == 1      # dp does not divide it
    assert mesh_mod.get_mesh() is None            # scope restored


_RECORDED = '''
HloModule jit_train_step

%body.1 (p: (s32[], bf16[16,512,768])) -> (s32[], bf16[16,512,768]) {
  %p = (s32[], bf16[16,512,768]{2,1,0}) parameter(0)
  %pallas.flash_attention_packed.21 = (bf16[16,512,768]{2,1,0:T(8,128)(2,1)}, f32[16,6,2,512]{3,2,1,0}) custom-call(%a, %b, %c, %d, %e), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}, bf16[16,512,768]{2,1,0}, bf16[16,512,768]{2,1,0}, bf16[16,512,768]{2,1,0}, f32[16,1,512]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(train_step)/jvp()/while/body/self_attn/pallas.flash_attention_packed/flash_packed_fwd/pallas_call" stack_frame_id=156}, backend_config={"custom_call_config":{"body":"TUzv"}}
  %call.3 = bf16[8192,768]{1,0} call(%x), to_apply=%wrapped.7
}

%wrapped.7 (q: bf16[8192,768]) -> bf16[8192,768] {
  %pallas.fused_rdln.23 = (bf16[8192,768]{1,0}, f32[1,8192]{1,0}, f32[1,8192]{1,0}) custom-call(%s, %q, %r, %w, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}, bf16[8192,768]{1,0}, bf16[8192,768]{1,0}, bf16[1,768]{1,0}, bf16[1,768]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(train_step)/jvp()/while/body/pallas.fused_rdln/rdln_fwd/pallas_call" stack_frame_id=216}, backend_config={"custom_call_config":{"body":"TUzv"}}
}

ENTRY %main.9 (x: bf16[16,512,768]) -> bf16[16,512,768] {
  %while.1 = (s32[], bf16[16,512,768]{2,1,0}) while(%t), condition=%cond.1, body=%body.1
  %pallas.fused_layer_norm.4 = (bf16[8192,768]{1,0}, f32[1,8192]{1,0}, f32[1,8192]{1,0}) custom-call(%x, %w, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[8192,768]{1,0}, bf16[1,768]{1,0}, bf16[1,768]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(train_step)/jvp(ErnieEmbeddings)/layer_norm/pallas.fused_layer_norm/ln_fwd/pallas_call" stack_frame_id=87}, backend_config={"custom_call_config":{"body":"TUzv"}}
}
'''


def test_hlo_readers_on_a_recorded_tpu_program(monkeypatch):
    """The smoke's readers against lines cut from a real v5e-compiled step:
    kernel names from op_name, operand shapes from the layout constraints,
    while-body membership through called computations."""
    calls = cs.mosaic_calls(_RECORDED)
    assert [c["kernel"] for c in calls] == [
        "flash_packed_fwd", "rdln_fwd", "ln_fwd"]
    assert calls[0]["operands"][1] == (16, 512, 768)
    (body,) = cs.scanned_bodies(_RECORDED)
    assert {c["kernel"] for c in cs.mosaic_calls(body)} == {
        "flash_packed_fwd", "rdln_fwd"}     # rdln via the called computation
    # the full kernel set is not in this cut: the check names what is missing
    with pytest.raises(AssertionError, match="flash_packed_dkdv"):
        cs.check_kernels_in_program(_RECORDED)
    monkeypatch.setattr(cs, "SCANNED_KERNELS",
                        ("flash_packed_fwd", "rdln_fwd"))
    monkeypatch.setattr(cs, "HEAD_KERNELS", ("ln_fwd",))
    cs.check_kernels_in_program(_RECORDED)
    # per-chip batch 16 of a global 64: passes; claimed per-chip 64: fails
    cs.check_per_chip_batch(_RECORDED, 16, 64, 512)
    with pytest.raises(AssertionError, match="GLOBAL batch"):
        cs.check_per_chip_batch(_RECORDED, 4, 16, 512)
    gathered = _RECORDED + (
        "  %ag = bf16[64,512,768]{2,1,0} all-gather(%x), dimensions={0}\n")
    with pytest.raises(AssertionError, match="all-gather"):
        cs.check_per_chip_batch(gathered, 16, 64, 512)


def test_script_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""      # no result line of any kind


def test_result_line_has_exactly_the_contract_keys():
    """The driver parses the last stdout line: {"ok", "device"} and nothing
    else; device = {"platform", "kind", "count"} as JAX reports them."""
    import json
    line = cs.result_line(jax.devices())
    assert "\n" not in line
    out = json.loads(line)
    assert set(out) == {"ok", "device"} and out["ok"] is True
    assert out["device"] == {"platform": jax.devices()[0].platform,
                             "kind": jax.devices()[0].device_kind,
                             "count": len(jax.devices())}
    assert type(out["device"]["count"]) is int
