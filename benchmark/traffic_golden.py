"""A serving mix's lengths in one fixed low-discrepancy order: the mix's
`"order": "golden"`.

`traffic.Traffic` shuffles the `BLOCK` quantile midpoints of each length
distribution inside blocks of 32, seeded, so that any stretch of a run
carries the same mix of sizes. That holds for a stretch of many blocks; a
window that finishes a dozen long requests sees a seeded half of one block,
and its work moves with the seed. Here request i takes the midpoint at
frac(i * phi) of the prompt lengths and at frac(i * (sqrt 2 - 1)) of the
output lengths (Kronecker sequences: every run of k consecutive requests
spreads over the quantiles to within O(log k / k), and since 1, phi and
sqrt 2 are independent over the rationals the pairs are spread over the
joint distribution, prompt and output independent, as the seeded draws are).
The lengths are the same under every seed; the seed draws the token ids as
`Traffic` does, and the driver the weights.

    make(mix, vocab, seed)   the mix's generator: this one for "golden",
                             `traffic.Traffic` for a mix without "order"
"""
from __future__ import annotations

from benchmark.traffic import BLOCK, Traffic

PHI = (5 ** 0.5 - 1) / 2
SILVER = 2 ** 0.5 - 1


def quantile(i: int, step: float) -> int:
    """Index of request i's quantile midpoint among `BLOCK`."""
    return int(BLOCK * ((i * step) % 1.0))


class GoldenTraffic(Traffic):
    """`Traffic` with the lengths in the fixed order above. Closed-loop mixes
    only: an open loop's gaps stay `Traffic`'s."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        super().__init__(mix, vocab, seed)
        if self.kind != "closed":
            raise ValueError("the golden order is for closed-loop mixes")

    def request(self, i: int):
        body = i // self.reuse
        n_prompt = int(self._plens[quantile(body, PHI)])
        n_out = int(self._olens[quantile(i, SILVER)])
        ids = self._rng("ids", body).integers(1, self.vocab, n_prompt)
        return self.shared + ids.tolist(), n_out


def make(mix: dict, vocab: int, seed: int) -> Traffic:
    order = mix.get("order")
    if order is None:
        return Traffic(mix, vocab, seed)
    if order == "golden":
        return GoldenTraffic(mix, vocab, seed)
    raise ValueError(f"unknown length order {order!r}")
