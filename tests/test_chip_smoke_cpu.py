"""chip_smoke.py's serve, train and --chips 4 phases, in-process on the CPU.

Rehearsals 1 and 2 of the on-chip-measurement guide kept as tests: the same
phase functions the chip run calls, at `LlamaConfig.tiny()` sizes, on the
conftest's virtual devices, with Pallas in interpret mode. Sizes are steered
here by calling the phases with small arguments — chip_smoke.py has no option
for it — and the device check is monkeypatched where `main` itself runs.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.models import LlamaConfig  # noqa: E402

# tiny engine geometry: 8-token pages, 16-token buckets, f32 end to end so
# the first tokens must equal the reference's argmax exactly
ENGINE = dict(slots=4, prompt_bucket=16, block_size=8, dtype=jnp.float32)
LENS, SHARED = (41, 12, 20, 37), 24


def _cfg(**over):
    return LlamaConfig.tiny(max_position_embeddings=128, **over)


def test_serve_phase_tiny(capsys):
    cfg = _cfg()
    prompts = chip_smoke.make_prompts(cfg.vocab_size, LENS, SHARED, seed=0)
    assert prompts[-1][:SHARED] == prompts[0][:SHARED]
    eng, tokens = chip_smoke.serve_phase(
        cfg, prompts, SHARED, seed=0, max_prompt_len=64, max_new=8, **ENGINE)
    assert [len(t) for t in tokens] == [8] * len(LENS)
    assert eng.metrics()["prefix_hit_tokens"] >= SHARED
    out = capsys.readouterr().out
    assert "first tokens agree with the f32 reference" in out
    assert "compiles after warm(): 0" in out


def test_serve_phase_refuses_a_wrong_first_token(monkeypatch):
    """The reference comparison has teeth: a reference that disagrees with
    the engine fails the phase."""
    cfg = _cfg()
    prompts = chip_smoke.make_prompts(cfg.vocab_size, LENS[:2], 0, seed=0)
    real = chip_smoke.reference_last_logits
    monkeypatch.setattr(chip_smoke, "reference_last_logits",
                        lambda *a: -real(*a))
    with pytest.raises(SystemExit, match="trails the f32 reference"):
        chip_smoke.serve_phase(cfg, prompts, 0, seed=0, max_prompt_len=64,
                               max_new=4, **ENGINE)


def test_serve_depth_fits_memory():
    full = LlamaConfig.llama3_8b()
    assert chip_smoke.serve_depth(full, 16 * 2**30, 9000) in range(16, 32)
    assert chip_smoke.serve_depth(full, 400 * 2**30, 9000) == 32
    assert chip_smoke.serve_depth(full, 2**30, 9000) == 1


def test_train_phase_tiny(capsys):
    losses = chip_smoke.train_phase(_cfg(), 4, 32, steps=4, seed=0)
    assert len(losses) == 4 and losses[-1] < losses[0]
    assert "block_until_ready" in capsys.readouterr().out


def test_multichip_serve_tiny(capsys):
    # bf16 as on the chip (serving_mp ships the o-proj gather in bf16, so an
    # f32 stream is not comparable across degrees); kv heads must divide mp=4
    cfg = _cfg(num_key_value_heads=4, dtype="bfloat16")
    prompts = chip_smoke.make_prompts(cfg.vocab_size, LENS, SHARED, seed=1)
    chip_smoke.multichip_serve(cfg, prompts, SHARED, seed=1,
                               max_prompt_len=64, max_new=8, n=4,
                               **dict(ENGINE, dtype=jnp.bfloat16))
    out = capsys.readouterr().out
    assert "serve mp=4 vs mp=1" in out
    assert "KV pools" in out and "q/k/v projections" in out


def test_multichip_train_tiny(capsys):
    chip_smoke.multichip_train(_cfg(), 4, 32, seed=0, n=4)
    assert "train step loss one device" in capsys.readouterr().out


def test_main_fails_at_the_device_phase_without_a_chip(capsys):
    with pytest.raises(SystemExit, match="no accelerator"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_main_chips4_runs_only_the_cross_chip_phase(monkeypatch, capsys):
    """`--chips 4` with the device check steered: the cross-chip phase and
    what it is compared with, no other phase, count as the process saw."""
    ran = []
    monkeypatch.setattr(chip_smoke, "device_phase", lambda chips: {
        "platform": "tpu", "kind": "steered", "count": chips})
    for name in ("kernel_phase", "serve_phase", "train_phase",
                 "multichip_serve", "multichip_train"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda *a, _n=name, **k: ran.append(_n))
    chip_smoke.main(["--chips", "4"])
    assert ran == ["multichip_serve", "multichip_train"]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "steered", "count": 4}}
