"""The one traffic generator: a cell's `workloads/<name>.json` in, a seeded
request stream out.

A traffic mix is data — length distributions, arrival process, bursts,
sharing — and this file is the only code that reads it, so a later PR adds a
mix by adding a JSON file. Keys of a serving mix:

    arrivals      {"kind": "closed", "clients": N}   each client sends its
                  next request the instant its last one finishes
                  {"kind": "poisson", "rate_per_s": R, "lead_in_s": L,
                   "burst": B}   open loop: exponential gaps with mean B/R
                  between bursts of B requests (B defaults to 1); the first
                  L seconds of the same process run before the window opens
    prompt_len    {"dist": "lognormal", "median", "sigma", "min", "max"} —
    output_len    the user's own tokens / the tokens to generate
    shared_prefix_tokens   one seeded system prompt put before every prompt
    prompt_reuse  each prompt body is sent this many times in a row
                  (default 1): a document asked several questions

The seed changes the ORDER of the work and the token ids, never the work: the
lengths and gaps are the quantile midpoints of their distributions, laid out
in blocks of BLOCK and shuffled inside each block, so any stretch of a run —
under any seed — carries the same mix of sizes and the same offered load.
"""
from __future__ import annotations

import zlib
from statistics import NormalDist

import numpy as np

BLOCK = 32


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths_block(spec: dict, n: int = BLOCK) -> np.ndarray:
    """The `n` quantile midpoints of a clipped length distribution."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf(u) for u in _midpoints(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def gaps_block(mean_s: float, n: int = BLOCK) -> np.ndarray:
    """The `n` quantile midpoints of an exponential gap, scaled so that the
    block's mean is exactly `mean_s`."""
    g = -np.log1p(-_midpoints(n))
    return g * (mean_s / g.mean())


class Traffic:
    """Seeded, endless request stream of one mix. `request(i)` is the i-th
    request as `(prompt token ids, output length)`; `due(i)` its due time in
    seconds from the start of the arrival process (open loop only)."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.seed = int(seed)
        self.kind = mix["arrivals"]["kind"]
        if self.kind not in ("closed", "poisson"):
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self.reuse = int(mix.get("prompt_reuse", 1))
        self._plens = lengths_block(mix["prompt_len"])
        self._olens = lengths_block(mix["output_len"])
        n_shared = int(mix.get("shared_prefix_tokens", 0))
        self.shared = self._rng("shared").integers(
            1, self.vocab, n_shared).tolist()
        if self.kind == "poisson":
            a = mix["arrivals"]
            self.burst = int(a.get("burst", 1))
            self._gaps = gaps_block(self.burst / float(a["rate_per_s"]))
            self.lead_in_s = float(a.get("lead_in_s", 0.0))
        self._due = []          # due times computed so far

    def _rng(self, *what):
        # numpy hashes the whole sequence; seeds above 2**32 are fine
        words = [self.seed] + [zlib.crc32(w.encode()) if isinstance(w, str)
                               else int(w) for w in what]
        return np.random.default_rng(words)

    def _perm(self, what: str, block: int) -> np.ndarray:
        return self._rng(what, block).permutation(BLOCK)

    def request(self, i: int):
        body = i // self.reuse          # requests of one body share a prompt
        b, k = divmod(body, BLOCK)
        n_prompt = int(self._plens[self._perm("prompt", b)[k]])
        bo, ko = divmod(i, BLOCK)
        n_out = int(self._olens[self._perm("output", bo)[ko]])
        ids = self._rng("ids", body).integers(1, self.vocab, n_prompt)
        return self.shared + ids.tolist(), n_out

    def due(self, i: int) -> float:
        """Due time of request i, seconds after the arrival process starts."""
        while len(self._due) <= i:
            j = len(self._due)
            g, r = divmod(j, self.burst)
            if r:                               # same burst, same instant
                self._due.append(self._due[-1])
                continue
            b, k = divmod(g, BLOCK)
            gap = float(self._gaps[self._perm("gap", b)[k]])
            self._due.append((self._due[-1] if self._due else 0.0) + gap)
        return self._due[i]

    @property
    def max_prompt_tokens(self) -> int:
        return len(self.shared) + int(self.mix["prompt_len"]["max"])

    @property
    def max_output_tokens(self) -> int:
        return int(self.mix["output_len"]["max"])


def train_batch(vocab: int, batch: int, seq: int, seed: int, step: int):
    """Token ids and labels of training step `step` (0, 1, ...): fresh uniform
    ids from the seed, as int32 numpy arrays on the host."""
    rng = np.random.default_rng([int(seed), 7, int(step)])
    ids = rng.integers(0, vocab, (2, batch, seq), dtype=np.int32)
    return ids[0], ids[1]

