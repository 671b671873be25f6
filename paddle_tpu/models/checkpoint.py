"""Streaming checkpoint -> quantized serving layout.

The 7B-on-16GB bootstrap for REAL weights (round-4 VERDICT #5): a
Llama-2-7B bf16 state dict is 13.5 GB — materializing it on host or
device before quantizing defeats the point of weight-only serving. This
converter reads one tensor at a time (safetensors are lazily sliceable,
HF sharded-index layouts included), quantizes it on device, and frees
the fp copy before touching the next — peak transient is ONE fp weight.

Reference analog: python/paddle/framework/io.py:740 (paddle.load) +
the weight-only conversion feeding
python/paddle/nn/quant/quantized_linear.py:180 (weight_only_linear).
"""
from __future__ import annotations

import difflib
import json
import os
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, unwrap
from ..resilience import chaos
from ..resilience.retry import RetryPolicy, default_io_policy


def _nearest(name: str, candidates, n: int = 3) -> str:
    close = difflib.get_close_matches(name, list(candidates), n=n,
                                      cutoff=0.4)
    return f"; nearest keys: {close}" if close else ""


def _hf_name(our_name: str) -> str:
    """Our `_decode_params` key -> HF Llama checkpoint key."""
    if our_name.startswith("llama."):
        return "model." + our_name[len("llama."):]
    return our_name


def _needs_transpose(name: str, arr) -> bool:
    """HF torch nn.Linear stores [out, in]; our Linear stores [in, out].
    Embeddings are [vocab, h] in both."""
    return arr.ndim == 2 and "embed_tokens" not in name


class _SafetensorsSource:
    """name -> np.ndarray over a safetensors file or an HF sharded dir.
    Tensors are read one at a time; nothing else is resident. Shard
    reads retry transient IOErrors through `retry` (default: the shared
    io policy, FLAGS_io_retry_attempts attempts)."""

    def __init__(self, path: str, retry: Optional[RetryPolicy] = None):
        from safetensors import safe_open

        self._safe_open = safe_open
        self._path = path
        self._retry = retry if retry is not None else default_io_policy()
        self._by_file = {}
        if os.path.isdir(path):
            idx = os.path.join(path, "model.safetensors.index.json")
            if os.path.exists(idx):
                with open(idx) as f:
                    weight_map = json.load(f)["weight_map"]
                for name, fname in weight_map.items():
                    self._by_file[name] = os.path.join(path, fname)
            else:
                files = sorted(f for f in os.listdir(path)
                               if f.endswith(".safetensors"))
                if not files:
                    raise FileNotFoundError(
                        f"no .safetensors files under {path}")
                for fname in files:
                    full = os.path.join(path, fname)
                    with safe_open(full, framework="pt") as sf:
                        for name in sf.keys():
                            self._by_file[name] = full
        else:
            with safe_open(path, framework="pt") as sf:
                for name in sf.keys():
                    self._by_file[name] = path

    def __contains__(self, name: str) -> bool:
        return name in self._by_file

    def __call__(self, name: str) -> np.ndarray:
        if name not in self._by_file:
            shards = sorted(set(self._by_file.values()))
            raise KeyError(
                f"tensor {name!r} not found in checkpoint {self._path!r} "
                f"({len(self._by_file)} tensors across "
                f"{len(shards)} shard file(s): "
                f"{[os.path.basename(s) for s in shards[:4]]}"
                f"{'...' if len(shards) > 4 else ''})"
                f"{_nearest(name, self._by_file)}")
        return self._retry.call(self._read, name)

    def _read(self, name: str) -> np.ndarray:
        # framework="pt" so bf16/fp16 checkpoints load (numpy has no
        # native bf16). The tensor ships at its STORED width — bf16
        # reinterpreted through ml_dtypes — and upcasts to fp32 on
        # device: host->device transfer is the bottleneck, and
        # bf16->fp32 is exact, so shipping fp32 would double the bytes
        # for nothing.
        import torch

        chaos.maybe_io_error("shard_read")
        with self._safe_open(self._by_file[name], framework="pt") as sf:
            t = sf.get_tensor(name)
        if t.dtype == torch.bfloat16:
            import ml_dtypes

            return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
        return t.numpy()


def load_quant_serving_params(cfg, source: Union[str, dict, Callable],
                              quant: Optional[str],
                              dtype=jnp.bfloat16,
                              names: str = "auto"):
    """Stream a checkpoint into the `_decode_params` serving layout.

    cfg: LlamaConfig of the checkpoint.
    source: a path to a .safetensors file / HF checkpoint dir, a
        name->array dict (e.g. the output of paddle.load), or a callable
        name->array for custom readers. Dict/callable use OUR names and
        layout ([in, out] projections); safetensors paths use HF names
        and torch layout (transposed on read).
    quant: None (dense bf16 serving), "weight_only_int8" or
        "weight_only_int4" — projection + head weights quantize ON
        DEVICE the moment they land; the fp copy is freed before the
        next tensor is read.
    names: "auto" (HF names for paths, ours otherwise), "hf", or "ours".

    Returns the dec_params dict build_quant_generate /
    build_paged_generate / serving.ContinuousBatchingEngine consume.
    """
    from ..nn.quant import weight_quantize

    if quant not in (None, "weight_only_int8", "weight_only_int4"):
        raise ValueError(f"unsupported quant {quant!r}")
    if isinstance(source, str):
        reader = _SafetensorsSource(source)
        hf_names = names in ("auto", "hf")
    elif isinstance(source, dict):
        reader = source.__getitem__
        hf_names = names == "hf"
    else:
        reader = source
        hf_names = names == "hf"

    def fetch(our_name, transpose_ok=True):
        key = _hf_name(our_name) if hf_names else our_name
        try:
            arr = np.asarray(reader(key))
        except KeyError as e:
            if isinstance(source, (str, _SafetensorsSource)):
                raise  # _SafetensorsSource already raised descriptively
            known = source.keys() if isinstance(source, dict) else ()
            raise KeyError(
                f"tensor {key!r} (for param {our_name!r}) not found in "
                f"the {type(source).__name__} checkpoint source"
                f"{_nearest(key, known)}") from e
        if hf_names and transpose_ok and _needs_transpose(key, arr):
            arr = arr.T
        return arr

    def quantized(our_name):
        # transfer at stored width, upcast to fp32 ON DEVICE (exact for
        # bf16/fp16 sources)
        w = jnp.asarray(fetch(our_name)).astype(jnp.float32)
        if quant is None:
            return w.astype(dtype)
        wq, sc = weight_quantize(Tensor(w), algo=quant)
        out = (unwrap(wq), unwrap(sc))
        del w  # the fp device copy dies here, before the next read
        return out

    p = {"llama.embed_tokens.weight":
         jnp.asarray(fetch("llama.embed_tokens.weight")).astype(dtype)}
    for i in range(cfg.num_hidden_layers):
        pre = f"llama.layers.{i}."
        for nm in ("input_layernorm.weight",
                   "post_attention_layernorm.weight"):
            p[pre + nm] = jnp.asarray(fetch(pre + nm)).astype(dtype)
        for nm in ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                   "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                   "mlp.gate_proj.weight", "mlp.up_proj.weight",
                   "mlp.down_proj.weight"):
            p[pre + nm] = quantized(pre + nm)
    p["llama.norm.weight"] = jnp.asarray(
        fetch("llama.norm.weight")).astype(dtype)
    if not cfg.tie_word_embeddings:
        p["lm_head.weight"] = quantized("lm_head.weight")
    return p
