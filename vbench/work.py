"""The work an exact collapsed-Gibbs sweep needs, from shapes alone.

This is the yardstick for `fit_mfu` and the kernel rooflines. It counts
what the algorithm must do at the model's true number of topics K, never
what an implementation happens to move (lane padding, gathered copies,
padded token slots are waste, not work):

- per token and sweep: read the token's document and word count rows
  (2 * K * 4 bytes), 12 bytes of token scalars (document, word, weight),
  write its new topic (4 bytes), and about 4 * K floating-point operations
  (three logarithms' worth of arithmetic and the comparison per topic);
- per model and sweep: read and write once each document row and each row
  of a word that occurs in the corpus (2 * (D + W) * K * 4 bytes).
"""

from __future__ import annotations

import dataclasses

BYTES_PER_COUNT = 4
TOKEN_SCALAR_BYTES = 12
TOKEN_WRITE_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scale(self, f: float) -> "Work":
        return Work(self.flops * f, self.bytes * f)

    def least_time(self, peaks: dict) -> tuple[float, str]:
        """(seconds, which bound binds) at the device's peaks."""
        t_flops = self.flops / peaks["flops_per_s"]
        t_bytes = self.bytes / peaks["bytes_per_s"]
        return (t_bytes, "bytes") if t_bytes >= t_flops else (
            t_flops, "flops")


ZERO = Work(0.0, 0.0)


def token_work(tokens: int, k: int) -> Work:
    """The per-token part of one sweep over `tokens` real tokens."""
    per = 2 * k * BYTES_PER_COUNT + TOKEN_SCALAR_BYTES + TOKEN_WRITE_BYTES
    return Work(flops=4.0 * k * tokens, bytes=float(per) * tokens)


def table_work(docs: int, words_used: int, k: int) -> Work:
    """Reading and writing the touched count rows once."""
    return Work(flops=0.0,
                bytes=2.0 * (docs + words_used) * k * BYTES_PER_COUNT)


def sweep_work(tokens: int, docs: int, words_used: int, k: int) -> Work:
    """One whole sweep of one model."""
    return token_work(tokens, k) + table_work(docs, words_used, k)
