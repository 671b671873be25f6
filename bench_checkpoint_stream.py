"""7B streaming-conversion dry run: disk shards -> quantized serving.

Round-5 VERDICT #7 at scale: prove the streaming converter
(models/checkpoint.load_quant_serving_params) lands a Llama-2-7B
checkpoint (13.5 GB bf16 on disk) in the int8 serving layout on a
16 GB chip WITHOUT ever materializing the fp model — then actually
serves from it. Writes random bf16 shards in the HF sharded-safetensors
layout first (one shard per layer, like real HF repos), streams them,
and reports load time + a decode-step sanity number.

Usage: python bench_checkpoint_stream.py [--keep] [workdir]
           [--inject io_error[:P]]
       python bench_checkpoint_stream.py --gang N [--steps S]
           [--inject preempt_host:K@S] [workdir]

--inject io_error[:P] arms the resilience chaos injector (seam
shard_read, default P=0.2) for the streaming load, proving the
RetryPolicy absorbs transient read faults on the full 7B path; the
JSON output then includes the injected-fault and retry counters.

--gang N (ISSUE 12) spawns an N-subprocess checkpoint gang through
`parallel.launch.GangSupervisor`: every worker stages per-host shards
and commits through the two-phase barrier protocol
(resilience/coordination.py), then restores through generation
agreement. Reports per-rank save / restore / barrier-wait timings and,
with --inject preempt_host:K@S (kill rank K at gang save #S, armed on
attempt 0 only), the recovery wall-clock from detected death to a
respawned gang that re-agreed on one generation.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time


# ---------------------------------------------------------------------------
# gang checkpoint/restore bench (--gang N)
# ---------------------------------------------------------------------------

_GANG_WORKER = r"""
import json, os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from paddle_tpu.resilience import CheckpointManager, chaos
from paddle_tpu.resilience import coordination

ckpt_dir, out_dir, n_saves, mb = (sys.argv[1], sys.argv[2],
                                  int(sys.argv[3]), float(sys.argv[4]))
coord = coordination.from_env()
mgr = CheckpointManager(ckpt_dir, max_to_keep=3, coordinator=coord)
rng = np.random.default_rng(coord.rank)
# ~mb MiB of per-host "model state" in a few leaves
n = max(1, int(mb * 2**20 / 4 / 4))
state = {f"w{i}": rng.normal(size=(n,)).astype(np.float32)
         for i in range(4)}

start = 0
try:
    ck = mgr.restore()
    start = int(ck.meta.get("save_index", 0))
except coordination.CheckpointNotFoundError:
    pass

saves = []
for i in range(start, n_saves):
    chaos.on_step("gang_save", i + 1)   # preempt_host:K@S fires here
    t0 = time.perf_counter()
    mgr.save(state, step=i + 1, meta={"save_index": i + 1})
    saves.append(time.perf_counter() - t0)

t0 = time.perf_counter()
ck = mgr.restore()
restore_s = time.perf_counter() - t0
with open(os.path.join(out_dir,
                       f"rank{coord.rank}-a{coord.attempt}.json"),
          "w") as f:
    json.dump({"rank": coord.rank, "attempt": coord.attempt,
               "resumed_from": start, "generation": ck.generation,
               "save_s": saves, "restore_s": restore_s,
               "barrier_wait_s": round(coord.barrier_wait_s, 4),
               "n_barriers": coord.n_barriers}, f)
"""


def run_gang(nprocs: int, root: str, inject: str, n_saves: int,
             mb_per_host: float):
    from paddle_tpu.parallel.launch import GangSupervisor

    os.makedirs(root, exist_ok=True)
    ck, out, store = (os.path.join(root, d)
                      for d in ("ck", "out", "store"))
    for p in (ck, out, store):
        shutil.rmtree(p, ignore_errors=True)
        os.makedirs(p)
    worker = os.path.join(root, "gang_worker.py")
    with open(worker, "w") as f:
        f.write(_GANG_WORKER)

    def env(rank, attempt):
        e = {"PYTHONPATH": os.path.dirname(os.path.abspath(__file__))
             + os.pathsep + os.environ.get("PYTHONPATH", ""),
             "PADDLE_TPU_BARRIER_TIMEOUT_S":
                 os.environ.get("PADDLE_TPU_BARRIER_TIMEOUT_S", "15"),
             # a preemption is a ONE-SHOT external event: armed on the
             # first attempt only, or the relaunched rank would be
             # re-killed when it replays the same save index
             "PADDLE_TPU_CHAOS": (inject or "") if attempt == 0 else ""}
        return e

    print(json.dumps({"stage": "gang_start", "nprocs": nprocs,
                      "n_saves": n_saves, "mb_per_host": mb_per_host,
                      "inject": inject or None}), flush=True)
    sup = GangSupervisor(
        [sys.executable, worker, ck, out, str(n_saves),
         str(mb_per_host)],
        nprocs, store_dir=store, max_restarts=2, env=env,
        terminate_grace_s=2.0)
    t0 = time.perf_counter()
    res = sup.run(timeout=600)
    wall = time.perf_counter() - t0
    if not res.success:
        logs = sorted(os.listdir(os.path.join(store, "logs")))
        print(json.dumps({"stage": "gang_failed",
                          "result": res.as_dict(), "logs": logs}),
              flush=True)
        raise SystemExit(1)
    import glob

    rows = [json.load(open(p)) for p in
            sorted(glob.glob(os.path.join(out, "rank*-a*.json")))]
    final = [r for r in rows
             if r["attempt"] == max(x["attempt"] for x in rows)]
    gens = {r["generation"] for r in final}
    for r in rows:
        r["save_s"] = [round(s, 4) for s in r["save_s"]]
        r["restore_s"] = round(r["restore_s"], 4)
        print(json.dumps({"stage": "gang_rank", **r}), flush=True)
    all_saves = [s for r in rows for s in r["save_s"]]
    print(json.dumps({
        "stage": "gang_summary", "nprocs": nprocs,
        "attempts": res.attempts, "wall_s": round(wall, 2),
        "recovery_wall_s": round(res.recovery_wall_s, 3),
        "restarts": [list(x) for x in res.restarts],
        "agreed_generation": sorted(gens),
        "one_agreed_generation": len(gens) == 1,
        "save_s_mean": round(sum(all_saves) / max(len(all_saves), 1), 4),
        "save_s_max": round(max(all_saves, default=0.0), 4),
        "restore_s_mean": round(sum(r["restore_s"] for r in final)
                                / len(final), 4),
        "barrier_wait_s": {r["rank"]: r["barrier_wait_s"]
                           for r in final},
    }), flush=True)
    if len(gens) != 1:
        raise SystemExit("gang did NOT converge on one generation")


def write_shards(cfg, root):
    import torch
    from safetensors.torch import save_file

    os.makedirs(root, exist_ok=True)
    gen = torch.Generator().manual_seed(0)
    h, dh = cfg.hidden_size, cfg.head_dim
    nh, nkv, im = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.intermediate_size)

    def rnd(*shape):
        # bf16 like real HF Llama-2 checkpoints; torch layout [out, in]
        return (torch.randn(*shape, generator=gen) * 0.02).to(
            torch.bfloat16)

    weight_map, total = {}, 0

    def shard(fname, tensors):
        nonlocal total
        save_file(tensors, os.path.join(root, fname))
        for k, t in tensors.items():
            weight_map[k] = fname
            total += t.numel() * t.element_size()

    shard("model-embed.safetensors",
          {"model.embed_tokens.weight": rnd(cfg.vocab_size, h),
           "model.norm.weight": torch.ones(h, dtype=torch.bfloat16),
           "lm_head.weight": rnd(cfg.vocab_size, h)})
    for i in range(cfg.num_hidden_layers):
        pre = f"model.layers.{i}."
        shard(f"model-{i:05d}.safetensors", {
            pre + "input_layernorm.weight":
                torch.ones(h, dtype=torch.bfloat16),
            pre + "post_attention_layernorm.weight":
                torch.ones(h, dtype=torch.bfloat16),
            pre + "self_attn.q_proj.weight": rnd(nh * dh, h),
            pre + "self_attn.k_proj.weight": rnd(nkv * dh, h),
            pre + "self_attn.v_proj.weight": rnd(nkv * dh, h),
            pre + "self_attn.o_proj.weight": rnd(h, nh * dh),
            pre + "mlp.gate_proj.weight": rnd(im, h),
            pre + "mlp.up_proj.weight": rnd(im, h),
            pre + "mlp.down_proj.weight": rnd(h, im),
        })
    with open(os.path.join(root, "model.safetensors.index.json"),
              "w") as f:
        json.dump({"weight_map": weight_map}, f)
    return total


def _pop_opt(argv, name):
    """Remove `name VALUE` from argv; returns (argv, VALUE or None)."""
    if name not in argv:
        return argv, None
    at = argv.index(name)
    if at + 1 >= len(argv):
        raise SystemExit(f"{name} needs a value")
    val = argv[at + 1]
    return argv[:at] + argv[at + 2:], val


def main():
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    enable_compile_cache()   # the one decision where the cache lives
    argv = sys.argv[1:]
    argv, gang = _pop_opt(argv, "--gang")
    argv, steps = _pop_opt(argv, "--steps")
    argv, mb = _pop_opt(argv, "--mb")
    keep = "--keep" in argv
    argv, spec = _pop_opt(argv, "--inject")
    inject = None
    if spec is not None:
        kind = spec.partition(":")[0]
        if gang is not None:
            if kind != "preempt_host":
                raise SystemExit(
                    f"--gang --inject supports preempt_host:K@S, "
                    f"got {spec!r}")
            inject = spec
        elif kind == "io_error":
            p = spec.partition(":")[2]
            inject = f"io_error:{p or 0.2}:shard_read"
        else:
            raise SystemExit(f"--inject supports io_error[:P], got {spec!r}")
    args = [a for a in argv if a != "--keep"]
    if gang is not None:
        run_gang(int(gang), args[0] if args else "/tmp/ptpu_gang_bench",
                 inject, int(steps or 8), float(mb or 4.0))
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import (LlamaConfig, build_quant_generate,
                                   load_quant_serving_params)

    root = args[0] if args else "/tmp/llama7b_shards"
    cfg = LlamaConfig.llama2_7b(dtype="bfloat16")

    retry_stats = None
    if inject:
        from paddle_tpu.resilience import chaos

        chaos.install(inject, seed=0)
        print(json.dumps({"stage": "chaos_armed", "spec": inject}),
              flush=True)

    t0 = time.perf_counter()
    disk_bytes = write_shards(cfg, root)
    t_write = time.perf_counter() - t0
    print(json.dumps({"stage": "shards_written",
                      "disk_gb": round(disk_bytes / 2**30, 2),
                      "s": round(t_write, 1)}), flush=True)

    t0 = time.perf_counter()
    if inject:
        # explicit source so the retry telemetry is reportable; the
        # load path is identical to the plain string route. 8 attempts:
        # at P=0.2 a 291-shard 7B read gives up with prob ~1e-6 per
        # tensor, so the bench measures absorption, not luck
        from paddle_tpu.models.checkpoint import _SafetensorsSource
        from paddle_tpu.resilience.retry import RetryPolicy

        src = _SafetensorsSource(root, retry=RetryPolicy(
            max_attempts=8, base_delay=0.01, max_delay=0.5))
        p = load_quant_serving_params(cfg, src, "weight_only_int8",
                                      names="hf")
        retry_stats = src._retry.stats
    else:
        p = load_quant_serving_params(cfg, root, "weight_only_int8")
    np.asarray(jax.tree.leaves(p)[-1])
    t_load = time.perf_counter() - t0
    hbm = sum(x.nbytes for x in jax.tree.leaves(p))
    rec = {"stage": "streamed_quantized", "s": round(t_load, 1),
           "hbm_gb": round(hbm / 2**30, 2)}
    if retry_stats is not None:
        from paddle_tpu.resilience import chaos

        rec["injected_faults"] = chaos.counters()
        rec["retry"] = retry_stats.as_dict()
    print(json.dumps(rec), flush=True)

    # serve from the streamed layout: short prefill + a few decode steps
    b, sb, max_new = 4, 128, 8
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, sb)))
    fn = jax.jit(build_quant_generate(cfg, b, sb, max_new))
    t0 = time.perf_counter()
    toks = np.asarray(fn(p, ids, jnp.asarray(sb, jnp.int32),
                         jax.random.PRNGKey(0),
                         jnp.asarray(1.0, jnp.float32),
                         jnp.asarray(1.0, jnp.float32)))
    t_gen = time.perf_counter() - t0
    ok = bool((toks >= 0).all() and (toks < cfg.vocab_size).all()
              and np.unique(toks).size > 1)
    print(json.dumps({"stage": "served", "compile_plus_gen_s":
                      round(t_gen, 1), "tokens_shape": list(toks.shape),
                      "sane": ok}), flush=True)
    if not keep:
        shutil.rmtree(root)


if __name__ == "__main__":
    main()
