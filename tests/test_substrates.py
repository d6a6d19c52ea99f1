"""Substrate tests: data pipeline determinism, AdamW, gradient compression,
checkpoint save/restore (incl. corruption + crash recovery), the
fault-tolerant driver, the straggler monitor, and pipeline parallelism
(subprocess with 8 virtual devices)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compress import (compress_grads, decompress_grads,
                                  init_error_state)
from repro.runtime.driver import FaultTolerantTrainer, TransientError
from repro.runtime.straggler import StragglerMonitor


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_deterministic_and_resumable():
    cfg = DataConfig(vocab=97, seq_len=32, global_batch=8)
    a = SyntheticLM(cfg)
    b = SyntheticLM(cfg)
    for s in (0, 5, 1000):
        np.testing.assert_array_equal(a.batch_at(s)["tokens"],
                                      b.batch_at(s)["tokens"])
    assert not np.array_equal(a.batch_at(1)["tokens"],
                              a.batch_at(2)["tokens"])


def test_data_host_sharding_disjoint():
    full = SyntheticLM(DataConfig(vocab=97, seq_len=16, global_batch=8))
    h0 = SyntheticLM(DataConfig(vocab=97, seq_len=16, global_batch=8,
                                host_id=0, n_hosts=2))
    h1 = SyntheticLM(DataConfig(vocab=97, seq_len=16, global_batch=8,
                                host_id=1, n_hosts=2))
    assert h0.host_batch == 4 and h1.host_batch == 4
    assert not np.array_equal(h0.batch_at(0)["tokens"],
                              h1.batch_at(0)["tokens"])


def test_data_labels_are_shifted_tokens():
    d = SyntheticLM(DataConfig(vocab=97, seq_len=16, global_batch=2))
    b = d.batch_at(0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert b["loss_mask"][:, -1].sum() == 0


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_converges_on_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      schedule="constant", moment_dtype="float32")
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(cfg, params)
    for _ in range(200):
        g = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(cfg, g, opt, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_bf16_moments_still_converge():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      schedule="constant", moment_dtype="bfloat16")
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(cfg, params)
    assert opt["mu"]["w"].dtype == jnp.bfloat16
    for _ in range(300):
        g = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(cfg, g, opt, params)
    assert float(jnp.abs(params["w"]).max()) < 5e-2


def test_grad_clip_limits_update_norm():
    cfg = AdamWConfig(lr=1.0, clip_norm=1e-3, weight_decay=0.0,
                      warmup_steps=0, schedule="constant")
    params = {"w": jnp.ones(4)}
    opt = adamw_init(cfg, params)
    _, _, m = adamw_update(cfg, {"w": jnp.full(4, 1e6)}, opt, params)
    assert float(m["grad_norm"]) > 1e5     # raw norm reported


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_int8_compression_with_error_feedback_converges():
    """SGD on a quadratic with int8-compressed grads + error feedback must
    still converge (the error-feedback convergence guarantee)."""
    w = jnp.asarray([4.0, -2.0, 1.0])
    err = init_error_state({"w": w})
    lr = 0.05
    for _ in range(300):
        g = {"w": 2 * w}
        q, s, err = compress_grads(g, err)
        deq = decompress_grads(q, s)
        w = w - lr * deq["w"]
    assert float(jnp.abs(w).max()) < 1e-2


def test_int8_quantization_bounded_error():
    x = jnp.linspace(-3, 3, 101)
    from repro.optim.compress import quantize_int8, dequantize_int8
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    assert float(jnp.abs(dequantize_int8(q, s) - x).max()) <= float(s) / 2 + 1e-6


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def _tiny_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (4, 3)),
                       "b": jnp.zeros(3)},
            "opt": {"step": jnp.asarray(7, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(tmp_path)
    st = _tiny_state()
    cm.save(10, st, blocking=True)
    assert cm.latest_step() == 10
    out = cm.restore(10, jax.eval_shape(lambda: st))
    np.testing.assert_allclose(out["params"]["w"], st["params"]["w"])
    assert int(out["opt"]["step"]) == 7


def test_checkpoint_async_and_gc(tmp_path):
    cm = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        cm.save(s, _tiny_state(s))
    cm.wait()
    assert cm.latest_step() == 4
    steps = sorted(int(p.name.split("_")[1])
                   for p in Path(tmp_path).glob("step_*"))
    assert len(steps) <= 2


def test_checkpoint_skips_torn_save(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(5, _tiny_state(), blocking=True)
    torn = Path(tmp_path) / "step_000000009"
    torn.mkdir()
    (torn / "meta.json").write_text("{}")      # no COMMIT marker
    assert cm.latest_step() == 5


def test_checkpoint_detects_corruption(tmp_path):
    cm = CheckpointManager(tmp_path)
    st = _tiny_state()
    cm.save(3, st, blocking=True)
    d = Path(tmp_path) / "step_000000003"
    flat = dict(np.load(d / "shard_00000.npz"))
    flat["params/w"] = flat["params/w"] + 1.0
    np.savez(d / "shard_00000.npz", **flat)
    with pytest.raises(IOError):
        cm.restore(3, jax.eval_shape(lambda: st))


# ---------------------------------------------------------------------------
# fault-tolerant driver
# ---------------------------------------------------------------------------
def _toy_problem():
    def loss(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def train_step(state, batch):
        l, g = jax.value_and_grad(loss)(state["params"], batch)
        params = jax.tree.map(lambda p, gg: p - 0.1 * gg,
                              state["params"], g)
        return {"params": params}, {"loss": l}

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(3, 1))

    def batch_at(step):
        r = np.random.default_rng(step)
        x = r.normal(size=(16, 3)).astype(np.float32)
        return {"x": jnp.asarray(x),
                "y": jnp.asarray(x @ w_true, jnp.float32)}

    state = {"params": {"w": jnp.zeros((3, 1))}}
    return train_step, batch_at, state


def test_driver_trains_and_checkpoints(tmp_path):
    step_fn, batch_at, state = _toy_problem()
    tr = FaultTolerantTrainer(step_fn, CheckpointManager(tmp_path),
                              ckpt_every=10)
    rep, state = tr.run(state, batch_at, num_steps=40)
    assert rep.losses[-1] < rep.losses[0] * 0.2
    assert tr.ckpt.latest_step() is not None


def test_driver_recovers_from_transient_faults(tmp_path):
    step_fn, batch_at, state = _toy_problem()
    boom = {25}

    def fault(step):
        if step in boom:
            boom.clear()
            raise TransientError("injected")

    tr = FaultTolerantTrainer(step_fn, CheckpointManager(tmp_path),
                              ckpt_every=10, fault_hook=fault)
    rep, state = tr.run(state, batch_at, num_steps=40)
    assert rep.restarts == 1
    assert rep.end_step == 40


def test_driver_resumes_across_process_restart(tmp_path):
    """Simulated crash: run 20 steps, drop everything, build a fresh driver
    from the same directory — it must resume from the checkpoint."""
    step_fn, batch_at, state = _toy_problem()
    tr1 = FaultTolerantTrainer(step_fn, CheckpointManager(tmp_path),
                               ckpt_every=5)
    rep1, _ = tr1.run(state, batch_at, num_steps=20)

    step_fn2, batch_at2, fresh = _toy_problem()
    tr2 = FaultTolerantTrainer(step_fn2, CheckpointManager(tmp_path),
                               ckpt_every=5)
    rep2, final = tr2.run(fresh, batch_at2, num_steps=10)
    assert rep2.start_step == 20            # resumed, not restarted
    assert rep2.losses[0] < rep1.losses[0]  # picked up trained weights


# ---------------------------------------------------------------------------
# straggler monitor
# ---------------------------------------------------------------------------
def test_straggler_flags_outliers_only():
    m = StragglerMonitor()
    flags = [m.observe(i, 0.1 + 0.001 * (i % 3)) for i in range(30)]
    assert not any(flags)
    assert m.observe(30, 1.5)               # 15x the mean
    assert not m.observe(31, 0.1)


# ---------------------------------------------------------------------------
# pipeline parallelism (needs multiple devices -> subprocess)
# ---------------------------------------------------------------------------
PIPE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.pipeline import pipeline_forward, split_stages

mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
L, D, MB, M = 8, 16, 4, 8
key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (L, D, D)) * (1.0 / np.sqrt(D))}

def layer(p_l, x):
    return jnp.tanh(x @ p_l)

def stage_fn(p_stage, x):            # apply this stage's layer group
    def body(x, w):
        return layer(w, x), None
    y, _ = jax.lax.scan(body, x, p_stage["w"])
    return y

x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))

# sequential reference
def seq(params, xs):
    def body(x, w):
        return layer(w, x), None
    out = []
    for i in range(M):
        y, _ = jax.lax.scan(body, xs[i], params["w"])
        out.append(y)
    return jnp.stack(out)

ref = seq(params, x)
staged = split_stages(params, L, 4)
with mesh:
    out = pipeline_forward(stage_fn, mesh, "stage", staged, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                           rtol=1e-5)

# differentiability: grads must match the sequential program
def loss_pipe(p):
    with mesh:
        return jnp.sum(pipeline_forward(stage_fn, mesh, "stage",
                                        split_stages(p, L, 4), x) ** 2)
def loss_seq(p):
    return jnp.sum(seq(p, x) ** 2)
g1 = jax.grad(loss_pipe)(params)["w"]
g2 = jax.grad(loss_seq)(params)["w"]
np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4,
                           rtol=1e-4)
print("PIPELINE_OK")
"""


def test_pipeline_parallel_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", PIPE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=500)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
