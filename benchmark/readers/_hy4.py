"""What the `hy4-longctx-reason` readers share: the window's deltas of the
engine's counters (`raw["moe"]`: the routed layers' and the learned sparse
attention's `dsa_*`, each with its decode lane's part), turned into means a
decode step and a prefill window, and the trace's decode steps and windows. A
program that counts no `dsa_*` (the parent of the PR that brought the cell)
gives these readers nothing to read."""
from benchmark import arith_hy_v4 as arith
from benchmark.readers import _mellum

# the trace labels of the learned sparse attention's kernels
INDEX = ("dsa_index_scores",)
SPARSE = ("sparse_mla_decode", "sparse_mla_window")


def counters(ctx):
    c = ctx.get("moe") or {}
    return c if c.get("dsa_full_layer_steps_decode") else None


def per_lane(ctx):
    """{"decode": (keys scored, keys attended) a decode step, "window": the
    same a prefill window}, from the window's counters: a decode step runs
    every `full` layer once, so its steps are the full layer-steps over the
    full layers."""
    c = counters(ctx)
    if not c:
        return None
    full = arith.full_layers(ctx["config"])
    out = {}
    for lane, sfx in (("decode", "_decode"), ("window", None)):
        steps = c["dsa_full_layer_steps_decode"]
        scored, attended = c["dsa_keys_scored_decode"], \
            c["dsa_keys_attended_decode"]
        if sfx is None:
            steps = c["dsa_full_layer_steps"] - steps
            scored = c["dsa_keys_scored"] - scored
            attended = c["dsa_keys_attended"] - attended
        if steps:
            out[lane] = (scored * full / steps, attended * full / steps)
    return out


def traced(ctx):
    """(decode steps, prefill windows) the trace holds: every scheduling
    step runs `steps_per_sync` decode steps, a mixed one a window too."""
    return (_mellum.traced_steps(ctx) * ctx["steps_per_sync"],
            _mellum.traced_mixed_steps(ctx))


label_seconds = _mellum.label_seconds
