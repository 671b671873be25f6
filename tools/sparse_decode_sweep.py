"""Time `sparse_mla_decode` on the chip at `hy4-longctx-reason`'s decode shapes (24 sequences, 64 heads, 384-word
latent rows, 256-row pages, 2,048 selected of a 108-page table): the readings `DECODE_DEPTH` is set from. Needs a TPU;
prints one JSON line a reading, then all of them in one line.

- pages: every sequence selects n rows on each of 32 pages (n = 1 ... 64), each page copied whole; the kernel's time a
  page (mean over R calls chained in one program, so no launch is timed).
- lane: a selection as the cell makes it (random index scores over contexts of 6,144 ... 27,648, exact top-2,048):
  the kernel's time a call at each depth of pages in flight, the plan's (top-k excluded; R plans chained in one
  program), the pages a sequence reads, and the kernel against `sparse_decode_reference`.
python tools/sparse_decode_sweep.py [repeats]"""
import json, os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np
from paddle_tpu.kernels import sparse_attention as sa

assert jax.default_backend() == "tpu", "the sweep times the chip"
B, H, W, BS, COLS, K, PAGES = 24, 64, 384, 256, 108, 2048, 32
DEPTHS = (2, 3, 4, 6)
R = int(sys.argv[1]) if len(sys.argv) > 1 else 20
key = jax.random.key(42)
npool = B * COLS + 1
pool = jax.jit(lambda k: sa.pack_rows(jax.random.normal(k, (npool * BS, 576), jnp.bfloat16) * 0.5)[:, None])(key)
q = jax.random.normal(jax.random.fold_in(key, 1), (B, H, 576), jnp.bfloat16)
sink = jax.random.normal(jax.random.fold_in(key, 2), (H,), jnp.float32)
tables = jnp.asarray(np.random.default_rng(3).permutation(npool - 1)[:B * COLS].reshape(B, COLS), jnp.int32)
plan_for = jax.jit(lambda i, n: sa.decode_plan(i, n, tables, BS))


def chained():
    """R kernel calls in one program, traced at the module's DECODE_DEPTH as it stands now (the pool an argument: a
    constant of ~1 GB would be compiled into the program)."""
    @jax.jit
    def run(plan, sink, q, pool):
        def one(i, s):
            out = sa.sparse_mla_decode(q, pool, plan, s, scale=1 / 16, value_width=512, block_size=BS)
            return s + out[0, :, 0].astype(jnp.float32) * 1e-30
        return jax.lax.fori_loop(0, R, one, sink)
    return run


def seconds(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return float(np.median(times))


out = {"device": jax.devices()[0].device_kind, "repeats": R, "depth": sa.DECODE_DEPTH, "pages": [], "lane": {}}
rng = np.random.default_rng(7)
kernel = chained()
for n in (1, 4, 16, 64):
    idx = np.zeros((B, K), np.int32)
    for b in range(B):
        cols = rng.choice(COLS, PAGES, replace=False)
        pos = (cols[:, None] * BS + np.stack([rng.choice(BS, n, replace=False) for _ in cols])).ravel()
        idx[b, :len(pos)] = rng.permutation(pos)
    plan = plan_for(jnp.asarray(idx), jnp.full((B,), PAGES * n, jnp.int32))
    row = {"n": n, "page_us": seconds(kernel, plan, sink, q, pool) / R / (B * PAGES) * 1e6}
    out["pages"].append(row)
    print(json.dumps(row), flush=True)

ctx = jnp.asarray(np.linspace(6144, 27648, B).astype(np.int32))
scores = jax.random.normal(jax.random.fold_in(key, 4), (B, COLS * BS), jnp.float32)
scores = jnp.where(jnp.arange(COLS * BS)[None] < ctx[:, None], scores, -jnp.inf)
_, idx = jax.jit(lambda s: sa.select_topk(s, K))(scores)
n_sel = jnp.minimum(ctx, K)
plan = plan_for(idx, n_sel)
lane = out["lane"]


@jax.jit
def plans(idx, n_sel):
    # R plans chained through their page counts, so that none is hoisted
    def one(i, c):
        return c + jnp.sum(sa.decode_plan(jnp.where(c < 0, idx + 1, idx), n_sel, tables, BS).pages)
    return jax.lax.fori_loop(0, R, one, jnp.int32(0))


lane["plan_us"] = seconds(plans, idx, n_sel) / R * 1e6
lane["pages_mean"] = float(jnp.mean(plan.pages))
default = sa.DECODE_DEPTH
for depth in DEPTHS:
    sa.DECODE_DEPTH = depth
    lane[f"kernel_us_depth{depth}"] = seconds(chained(), plan, sink, q, pool) / R * 1e6
    print(json.dumps({"depth": depth, "kernel_us": lane[f"kernel_us_depth{depth}"]}), flush=True)
sa.DECODE_DEPTH = default
got = sa.sparse_mla_decode(q, pool, plan, sink, scale=1 / 16, value_width=512, block_size=BS)
rows = tables[jnp.arange(B)[:, None], idx // BS] * BS + idx % BS
want = sa.sparse_decode_reference(q, pool, rows, n_sel, sink, scale=1 / 16, value_width=512)
lane["max_abs_err"] = float(jnp.max(jnp.abs(got[..., :512].astype(jnp.float32) - want)))
lane["max_abs_ref"] = float(jnp.max(jnp.abs(want)))
print(json.dumps(lane), flush=True)
print(json.dumps(out), flush=True)
