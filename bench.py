"""Benchmark: flagship LM training throughput on the local TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}. Exits
non-zero when the backend is not a TPU.

The reference publishes no absolute ML-throughput numbers in-repo
(BASELINE.md — `published: {}`); its GPT-class benchmark is tracked in CI
only. So `vs_baseline` here is reported as model-FLOPs utilization (MFU)
against the chip's bf16 peak — a hardware-honest denominator that can only be
compared apples-to-apples: reference DeepSpeed GPT fine-tunes on A100s land
around 0.30-0.45 MFU, so vs_baseline >= ~0.35 means we match or beat the
reference's efficiency on our silicon.
"""

from __future__ import annotations

import json
import sys
import time

# Published bf16 peak FLOP/s per chip, keyed by `device_kind` as JAX reports
# it (a v5e is "TPU v5 lite"; Google Cloud documentation, "TPU v5e": 197
# TFLOP/s). A kind that is not here is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.core.jax_platform import use_compile_cache
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.transformer import lm_loss
    from ray_tpu.parallel.spmd import make_train_step

    # This process owns the chip for the train cell below; every
    # subprocess after it is pinned to the CPU.
    use_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench.py measures the chip and found backend "
                 f"{backend!r}; it does not shrink the model and carry on.")
    device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAK_BF16_FLOPS:
        sys.exit(f"no peak FLOP/s on record for device_kind "
                 f"{device_kind!r}; add it to PEAK_BF16_FLOPS with its "
                 f"source")
    # GPT-medium-class model (503M params); bf16 compute, fits one v5e
    # chip with float32 AdamW state. Sized so the GEMMs saturate the MXU:
    # the round-4 110M config (d_model 768) plateaued at 0.36 MFU because
    # [B*S,768]x[768,2048] tiles under-fill the systolic array — at
    # d_model 1536 the same measurement gives 0.47+ (PROFILE.md).
    # head_dim 128 (= the MXU/lane width): the Pallas flash kernel runs ~3x
    # faster than at head_dim 64, and every projection GEMM tiles cleanly.
    # remat_policy="save_attn_qkv": backward skips recomputing the flash
    # kernel and the QKV projection (the two priciest recomputes) for
    # ~2.4 GB of saved activations.
    cfg = TransformerConfig(
        vocab_size=32768, d_model=1536, n_layers=12, n_heads=12, d_ff=6144,
        max_seq_len=1024, dtype=jnp.bfloat16, remat=True,
        remat_policy="save_attn_qkv")
    batch, seq = 16, 1024

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    optimizer = optax.adamw(1e-4)
    opt_state = optimizer.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size, "int32")
    train_batch = {"tokens": tokens}

    step = make_train_step(lambda p, b: lm_loss(p, b, cfg), optimizer)

    # Warmup/compile.
    params, opt_state, loss = step(params, opt_state, train_batch)
    jax.block_until_ready(loss)

    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, train_batch)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * iters / dt

    # MFU: 6*N FLOPs/token (fwd+bwd) over the chip's bf16 peak.
    mfu = (6.0 * n_params * tokens_per_sec) / PEAK_BF16_FLOPS[device_kind]

    # Runtime microbench (ray_perf equivalent): folded into the same JSON
    # line as `notes` so the driver's one-line contract holds. Includes
    # the compiled-graph micro-bench — a 3-actor chain via
    # experimental_compile().execute() vs the same chain through
    # dag.execute()'s per-task path (`cgraph_call_ms`,
    # `dag_chain_call_ms`, `cgraph_vs_dag_speedup`) — the round-8
    # task-plane trajectory (`tasks_inline_per_s` next to `tasks_per_s`:
    # the inline-vs-remote dispatch tiers) and, via --attribute, the
    # submit-path attribution breakdown (encode / lease / frame write /
    # push rtt / worker decode+exec, plus `submit.inline`/`submit.remote`
    # and `lease.batch_size`) so every bench line records where the
    # task-plane time went, not just how much there was.
    notes = {}
    try:
        import os
        import subprocess

        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.perf", "--scale", "0.5",
             "--attribute"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        notes["perf_bench_error"] = repr(e)
    try:
        # Worker-direct dispatch rings (round 10): the remote tiny-task
        # rate over driver->worker shm rings, with the zero-syscall
        # honesty counters (enqueues vs doorbells, fallbacks) — the
        # task-plane trajectory next to tasks_per_s/tasks_inline_per_s.
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.perf", "--ring",
             "--scale", "0.5"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes["ring"] = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        notes["ring_bench_error"] = repr(e)
    try:
        # Control plane at scale (round 14): lease grants/s and
        # placement-group 2PCs/s against a real GcsServer with 100
        # in-process simulated raylets — the cluster-property metric
        # next to the single-box ones, isolated from fork/exec noise.
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.perf", "--simcluster",
             "--scale", "0.5"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes["simcluster"] = json.loads(
            out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        notes["simcluster_bench_error"] = repr(e)
    try:
        # HA control plane (round 18): leader kill -9 -> first
        # quorum-acked write failover latency, replicated write-through
        # throughput, elections and replication lag on a 3-replica GCS
        # — the availability metrics next to the restart-time one.
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.perf", "--ha",
             "--scale", "0.5"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes["ha"] = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        notes["ha_bench_error"] = repr(e)
    try:
        # LLM-serving scenario (continuous-batching engine): sustained
        # tokens/s vs the static-batching baseline on the same mixed
        # workload, TTFT, shed-mode p99 under 2x overload, and the
        # prefix-sharing workload (warm-vs-cold tokens/s + TTFT on a
        # shared system prompt, with prefix_hit_tokens / cow_copies
        # honesty counters) — the north-star serving metrics next to
        # the training headline.
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.perf", "--llm-serve"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes["llm_serve"] = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        notes["llm_serve_error"] = repr(e)
    try:
        # Serving fleet (round 19): 3 replicas behind the KV-cache-
        # aware fleet router — warm-everywhere (cross-replica prefix
        # shipping) vs cold-per-replica tokens/s and TTFT, plus
        # seeded-kill conversation-recovery latency with the
        # zero-lost-conversations honesty counter.
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.perf", "--fleet"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes["fleet"] = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        notes["fleet_bench_error"] = repr(e)
    try:
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.rllib.bench"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes["rl_env_steps_per_sec"] = float(
            out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        # In-band failure record: a missing north-star metric must be
        # distinguishable from a broken bench.
        notes["rl_bench_error"] = repr(e)
    try:
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.rllib.bench", "--image"],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        notes["rl_image_env_steps_per_sec"] = float(
            out.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        notes["rl_image_bench_error"] = repr(e)

    print(json.dumps({
        "metric": "lm_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": f"tokens/s ({n_params/1e6:.0f}M-param LM, {backend}, "
                f"{device_kind})",
        "vs_baseline": round(mfu, 4),
        "notes": notes,
    }))


if __name__ == "__main__":
    main()
