"""Step-time attribution for the headline ALBERT bench (BASELINE.md).

Answers "where do the non-MFU cycles go?" with measurements, not guesses.

Measurement method — MARGINAL cost over in-program repetition: every host
call pays a fixed dispatch + readback cost, which swamps per-call timing of
sub-millisecond ops.  Every row here times ONE jitted program that repeats
the op K_LO and K_HI times via ``lax.scan`` and reports
(t_hi - t_lo) / (K_HI - K_LO): the fixed per-call term cancels and what is
left is device time per repeat.  Scan outputs are program outputs, so XLA
cannot dead-code-eliminate any iteration.

Stages:
  peak     — bf16 matmul ceiling actually achievable on this chip.
  pieces   — the step's matmul population in isolation (QKV/out proj, FFN,
             gathered MLM head) plus flash vs dense attention fwd & fwd+bwd.
  model    — whole-model fwd, fwd+bwd under each remat policy, LAMB apply,
             and the fused train step, each as marginal device time; the
             step row reports implied samples/s and MFU with the
             per-call dispatch cost cancelled.

Usage (on the TPU): python tools/profile_albert.py [peak|pieces|model|all]

Every row prints one JSON line so runs can be diffed; docs/perf.md holds the
analysis of the numbers committed from this tool.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dedloc_tpu.telemetry.steps import chip_peak_tflops


def _force(out):
    """Scalar readback: the timed region ends when the result is on the
    host (same convention as bench.py)."""
    leaf = jax.tree.leaves(out)[0]
    return float(jnp.asarray(leaf).ravel()[0])


def _time_once(f, *args):
    _force(f(*args))  # compile + settle
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        _force(f(*args))
        best = min(best, time.perf_counter() - start)
    return best


def marginal(make, label, flops=None, k_lo=4, k_hi=20, peak=None):
    """make(K) -> (jitted_fn, *args) repeating the op K times in-program.
    Prints marginal per-repeat device time (per-call dispatch cancelled)."""
    t_lo = _time_once(*make(k_lo))
    t_hi = _time_once(*make(k_hi))
    per = (t_hi - t_lo) / (k_hi - k_lo)
    row = {"label": label, "device_ms": round(per * 1e3, 3)}
    if flops is not None and per > 0:
        tf = flops / per / 1e12
        row["tflops_per_sec"] = round(tf, 1)
        row["vs_peak"] = round(tf / (peak or chip_peak_tflops()), 3)
    print(json.dumps(row), flush=True)
    return per


def scan_repeat(op, K, params, *args):
    """One jitted program running `op(params, *args)` K times. The scalar
    result of each iteration is folded back into `params` (×1e-30) so every
    iteration depends on the previous one — without this, XLA hoists the
    loop-invariant body and K never executes."""

    @jax.jit
    def f(p, *a):
        def body(p, _):
            val = op(p, *a)
            p = jax.tree.map(lambda x: x + val.astype(x.dtype) * 1e-30, p)
            return p, val

        _, ys = jax.lax.scan(body, p, None, length=K)
        return ys

    return (f, params, *args)


def chain_repeat(op, K, x0, *rest):
    """One jitted program chaining x -> op(x, *rest) K times (shape-preserving
    ops; serialises through the carry)."""

    @jax.jit
    def f(x, *r):
        def body(c, _):
            return op(c, *r), None

        out, _ = jax.lax.scan(body, x, None, length=K)
        return out

    return (f, x0, *rest)


def run_peak():
    M = 8192
    a = jnp.full((M, M), 0.5, jnp.bfloat16)
    b = jnp.full((M, M), 1.0 / M, jnp.bfloat16)
    per = marginal(
        lambda K: chain_repeat(jnp.dot, K, a, b),
        f"matmul_{M}x{M}x{M}", flops=2 * M**3,
    )
    peak = 2 * M**3 / per / 1e12
    print(json.dumps({"label": "achievable_peak_tflops", "value": round(peak, 1)}),
          flush=True)
    return peak


def run_pieces(peak):
    B, S, H, I, E, V, NH = 32, 512, 1024, 4096, 128, 30000, 16
    D = H // NH
    M = B * S

    x = jnp.full((M, H), 0.5, jnp.bfloat16)
    wp = jnp.full((H, H), 1.0 / H, jnp.bfloat16)
    marginal(lambda K: chain_repeat(jnp.dot, K, x, wp),
             "proj_16384x1024x1024 (QKV/out)", flops=2 * M * H * H, peak=peak)

    w1 = jnp.full((H, I), 1.0 / H, jnp.bfloat16)
    w2 = jnp.full((I, H), 1.0 / I, jnp.bfloat16)
    marginal(
        lambda K: chain_repeat(
            lambda c, a, b: jnp.dot(jnp.dot(c, a), b), K, x, w1, w2),
        "ffn_pair_1024x4096 + 4096x1024", flops=4 * M * H * I, peak=peak)

    mlm_m = B * 77
    xm = jnp.full((mlm_m, E), 0.5, jnp.bfloat16)
    wv = jnp.full((E, V), 1.0 / E, jnp.bfloat16)
    marginal(
        lambda K: chain_repeat(
            lambda c, w: jnp.dot(jnp.dot(c, w), w.T) / V, K, xm, wv),
        "mlm_vocab_pair_2464x128x30000", flops=4 * mlm_m * E * V, peak=peak)

    # attention: dense XLA vs Pallas flash, fwd and fwd+bwd
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, NH, D), jnp.bfloat16)
    kv_bias = jnp.zeros((B, S), jnp.float32)
    attn_flops = 4 * B * NH * S * S * D  # QK^T + AV

    def dense_attn(q, k, v, bias):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        logits = logits / np.sqrt(D) + bias[:, None, None, :]
        p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    from dedloc_tpu.ops.flash_attention import flash_attention

    impls = {"dense": dense_attn, "flash": lambda *a: flash_attention(*a)}
    for name, fn in impls.items():
        marginal(
            lambda K: chain_repeat(
                lambda c, bias: fn(c, c, c, bias).astype(jnp.bfloat16),
                K, q, kv_bias),
            f"attn_{name}_fwd", flops=attn_flops, peak=peak)
        grad_fn = jax.grad(
            lambda qq, bias: fn(qq, qq, qq, bias).astype(jnp.float32).sum())
        marginal(
            lambda K: chain_repeat(
                lambda c, bias: grad_fn(c, bias).astype(jnp.bfloat16),
                K, q, kv_bias),
            f"attn_{name}_fwd+bwd", flops=3 * attn_flops, peak=peak)


def make_model(remat_policy, impl):
    from dedloc_tpu.models.albert import (
        AlbertConfig,
        AlbertForPreTraining,
        fused_ln_for_policy,
    )

    cfg = AlbertConfig.large(remat_policy=remat_policy, attention_impl=impl,
                             fused_ln=fused_ln_for_policy(remat_policy))
    return AlbertForPreTraining(cfg), cfg


def make_batch(cfg, accum, per_step, seq, max_pred):
    host = np.random.default_rng(0)
    ids = host.integers(5, cfg.vocab_size, (accum, per_step, seq)).astype(np.int32)
    labelled = host.random((accum, per_step, seq)) < 0.15
    labelled &= np.cumsum(labelled, axis=2) <= max_pred
    positions = np.zeros((accum, per_step, max_pred), np.int32)
    label_ids = np.zeros((accum, per_step, max_pred), np.int32)
    weights = np.zeros((accum, per_step, max_pred), np.float32)
    for a in range(accum):
        for i in range(per_step):
            idx = np.flatnonzero(labelled[a, i])
            positions[a, i, : len(idx)] = idx
            label_ids[a, i, : len(idx)] = ids[a, i, idx]
            weights[a, i, : len(idx)] = 1.0
    return {
        "input_ids": jnp.asarray(ids),
        "attention_mask": jnp.ones((accum, per_step, seq), jnp.int32),
        "mlm_positions": jnp.asarray(positions),
        "mlm_label_ids": jnp.asarray(label_ids),
        "mlm_weights": jnp.asarray(weights),
        "sop_labels": jnp.asarray(
            host.integers(0, 2, (accum, per_step)), jnp.int32),
    }


def run_model(peak):
    from dedloc_tpu.data.mlm import max_predictions_for
    from dedloc_tpu.models.albert import albert_pretraining_loss_gathered
    from dedloc_tpu.optim import lamb
    from dedloc_tpu.parallel.train_step import TrainState

    import bench as headline

    accum, per_step, seq = 2, 12, 512  # round-4 headline recipe
    max_pred = max_predictions_for(seq)
    model, cfg = make_model("fused_ln", "flash")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((per_step, seq), jnp.int32))["params"]
    batch = make_batch(cfg, accum, per_step, seq, max_pred)
    mb = jax.tree.map(lambda x: x[0], batch)
    flops_sample = headline.albert_train_flops_per_sample(cfg, seq, max_pred)

    def loss_fn_for(m):
        def loss_fn(p, b, r):
            mlm, sop = m.apply({"params": p}, b["input_ids"],
                               b["attention_mask"],
                               mlm_positions=b["mlm_positions"])
            return albert_pretraining_loss_gathered(
                mlm, sop, b["mlm_label_ids"], b["mlm_weights"], b["sop_labels"])
        return loss_fn

    # whole-model forward (per micro-batch of 32)
    def fwd(p, b):
        mlm, _ = model.apply({"params": p}, b["input_ids"], b["attention_mask"],
                             mlm_positions=b["mlm_positions"])
        return mlm.astype(jnp.float32).mean()

    marginal(lambda K: scan_repeat(fwd, K, params, mb),
             f"model_fwd_only (B={per_step})",
             flops=per_step * flops_sample / 3,
             k_lo=2, k_hi=8, peak=peak)

    # fwd+bwd under each remat policy / attention impl (per micro-batch)
    for policy, impl in (("fused_ln", "flash"),
                         ("dots_no_batch_attn", "flash"),
                         ("dots_no_batch", "flash"), ("nothing", "flash"),
                         ("dots", "flash"), ("dots_no_batch", "dense"),
                         ("nothing", "dense")):
        m, _ = make_model(policy, impl)
        lf = loss_fn_for(m)

        def fwdbwd(p, b, r):
            g = jax.grad(lambda pp: lf(pp, b, r)[0])(p)
            # consume EVERY grad leaf: folding only one leaf into the probe
            # lets XLA dead-code-eliminate the other weight-grad matmuls,
            # under-reporting fwd+bwd by ~20% (the round-3 attribution's
            # "measurement residual" was exactly this artifact)
            return sum(x.mean() for x in jax.tree.leaves(g))

        label = f"fwdbwd_{policy}_{impl} (B={per_step})"
        try:
            marginal(
                lambda K: scan_repeat(fwdbwd, K, params, mb,
                                      jax.random.PRNGKey(1)),
                label, flops=per_step * flops_sample, k_lo=2, k_hi=8,
                peak=peak)
        except Exception as e:  # OOM etc.
            print(json.dumps({"label": label, "error": str(e)[:200]}),
                  flush=True)

    # LAMB apply alone (18M params: elementwise + per-tensor norms)
    tx = lamb(learning_rate=1.76e-3, weight_decay=0.01)
    state = jax.jit(lambda p: TrainState.create(p, tx))(params)

    def mk_apply(K):
        grads = jax.tree.map(lambda p: jnp.full_like(p, 1e-8, jnp.float32),
                             params)

        @jax.jit
        def f(state, grads):
            def body(s, _):
                updates, opt_state = tx.update(grads, s.opt_state, s.params)
                import optax
                return s.replace(
                    params=optax.apply_updates(s.params, updates),
                    opt_state=opt_state), s.step
            out, ys = jax.lax.scan(body, state, None, length=K)
            return ys
        return f, state, grads

    apply_t = marginal(mk_apply, "lamb_apply_only", k_lo=8, k_hi=72)

    # the full headline train step (accum=2 inside), marginal over steps
    from dedloc_tpu.parallel.train_step import make_local_train_step

    lf = loss_fn_for(model)
    step_inner = make_local_train_step(lf, tx, grad_accum_steps=accum)

    def mk_step(K):
        @jax.jit
        def f(state, batch, rng):
            def body(carry, _):
                s, r = carry
                r, sub = jax.random.split(r)
                s, metrics = step_inner(s, batch, sub)
                return (s, r), metrics["loss"]
            _, losses = jax.lax.scan(body, (state, rng), None, length=K)
            return losses
        return f, state, batch, jax.random.PRNGKey(1)

    samples = accum * per_step
    per = marginal(mk_step, f"full_train_step ({samples} samples)",
                   flops=samples * flops_sample, k_lo=2, k_hi=6, peak=peak)
    print(json.dumps({
        "label": "full_step_device_samples_per_sec",
        "value": round(samples / per, 2),
        "mfu_vs_197": round(samples / per * flops_sample / 197e12, 4),
        "lamb_share_of_step": round(apply_t / per, 4)}), flush=True)


def main():
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "backend": jax.default_backend()}), flush=True)
    peak = None
    if what in ("peak", "pieces", "model", "all"):
        peak = run_peak()
    if what in ("pieces", "all"):
        run_pieces(peak)
    if what in ("model", "all"):
        run_model(peak)


if __name__ == "__main__":
    main()
