"""The one traffic generator: a mix file's parameters in, seeded inputs out.

A mix (``traffic/<name>.json``) fixes the fleet size, the local steps per
round, the per-client batch and sequence length, the channel drift and the
split policy.  From ``--seed`` this module draws, independently of the
program under test:

* the fleet: client positions uniform in a 50 m disc and CPU clocks
  U[0.1, 2] GHz, 2,500 samples each (the paper's §IV-A set-up; the same
  draw as ``repro.core.latency.make_fleet``, copied so that the yardstick
  cannot move with the program);
* each client's token stream: a Zipf unigram corpus with a planted bigram
  successor for a quarter of the positions, windows of ``seq + 1`` tokens
  at seeded offsets into the client's own shard (the same construction as
  ``repro.data.SyntheticLM`` + ``LMBatcher``).

Every seed gets the same sizes; only the values differ.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent

MIX_KEYS = ("engine", "clients", "participation", "local_steps", "batch",
            "seq", "drift_m", "split_policy", "lr", "corpus_tokens",
            "zipf_exponent", "bigram_share", "followed_rounds",
            "trace_seconds")


def load_mix(name: str) -> Dict:
    path = HERE / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise KeyError(f"traffic mix {path} lacks {missing}")
    return mix


def sub_seeds(seed: int, names=("weights", "data", "fleet", "driver")
              ) -> Dict[str, int]:
    """Independent 31-bit seeds for each consumer, from any whole number."""
    state = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {n: int(s) & 0x7FFFFFFF for n, s in zip(names, state)}


@dataclasses.dataclass(frozen=True)
class Fleet:
    positions: np.ndarray      # (N, 2) metres
    cpu_hz: np.ndarray         # (N,)
    data_sizes: np.ndarray     # (N,) samples


def make_fleet(n: int, seed: int, radius_m: float = 50.0,
               f_min: float = 0.1e9, f_max: float = 2.0e9,
               data_size: int = 2500) -> Fleet:
    rng = np.random.default_rng(seed)
    rho = radius_m * np.sqrt(rng.uniform(size=n))
    phi = rng.uniform(0, 2 * np.pi, size=n)
    pos = np.stack([rho * np.cos(phi), rho * np.sin(phi)], axis=1)
    return Fleet(positions=pos, cpu_hz=rng.uniform(f_min, f_max, size=n),
                 data_sizes=np.full(n, data_size, np.int64))


def corpus(vocab: int, num_tokens: int, zipf_exponent: float,
           bigram_share: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_exponent
    probs /= probs.sum()
    toks = rng.choice(vocab, size=num_tokens, p=probs)
    succ = rng.permutation(vocab)
    follow = rng.random(num_tokens) < bigram_share
    toks[1:] = np.where(follow[1:], succ[toks[:-1]], toks[1:])
    return toks.astype(np.int32)


class TokenStream:
    """Client-stacked next-token batches: ``next()`` gives host arrays
    ``tokens`` (N, B, S) int32 and ``labels`` (N, B, S) int32."""

    def __init__(self, mix: Dict, vocab: int, seed: int):
        n, self.batch, self.seq = mix["clients"], mix["batch"], mix["seq"]
        toks = corpus(vocab, mix["corpus_tokens"], mix["zipf_exponent"],
                      mix["bigram_share"], seed)
        shard = len(toks) // n
        self.shards: List[np.ndarray] = [toks[i * shard:(i + 1) * shard]
                                         for i in range(n)]
        self.rngs = [np.random.default_rng([seed, i]) for i in range(n)]

    def next(self) -> Dict[str, np.ndarray]:
        toks, labels = [], []
        for shard, rng in zip(self.shards, self.rngs):
            starts = rng.integers(0, len(shard) - self.seq - 1,
                                  size=self.batch)
            win = np.stack([shard[s:s + self.seq + 1] for s in starts])
            toks.append(win[:, :-1])
            labels.append(win[:, 1:])
        return {"tokens": np.stack(toks).astype(np.int32),
                "labels": np.stack(labels).astype(np.int32)}
