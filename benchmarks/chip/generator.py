"""Traffic for the chip benchmark: a seed-made dataset split IID over the
CoDA workers, and the per-window sampler that feeds ``coda.fit``.

This is the benchmark's own copy of the program's synthetic draws (images:
two Gaussian class means over the pixels; tokens: positives over-sample a
motif token set), of its keep-every-positive imbalance and of its even IID
shard split, so that no change to the program can change the traffic.  A
mix's numbers (dataset size, positive share, batch, window length, sequence
length, signal) come from its file under ``traffic/``; the modality and the
input shape come from the configuration.

The sampler is the caller's input layer, as in ``launch/train.py``: one
NumPy draw of the window's indices, one gather on the host and one transfer
to the device per window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int, stream: int):
    """A JAX key for one stream of a run's randomness.  ``seed`` may exceed
    32 bits; its high word is folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def numpy_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def _draw(key, inputs: dict, traffic: dict, labels):
    """Inputs for ``labels`` [n]: images [n, hw*hw, 3] or tokens [n, S]."""
    n = labels.shape[0]
    signal = traffic["signal"]
    if inputs["kind"] == "images":
        hw = inputs["image_hw"]
        x = jax.random.normal(key, (n, hw * hw, 3))
        mean = (labels[:, None, None] * 2 - 1) * signal * 0.2
        return {"images": x + mean}
    if inputs["kind"] == "tokens":
        vocab, seq = inputs["vocab_size"], traffic["seq_len"]
        n_motif = max(1, int(vocab * traffic["motif_frac"]))
        k1, k2, k3 = jax.random.split(key, 3)
        base = jax.random.randint(k1, (n, seq), 0, vocab)
        motif = jax.random.randint(k2, (n, seq), 0, n_motif)
        use = jax.random.uniform(k3, (n, seq)) < signal * 0.25 * labels[:, None]
        return {"tokens": jnp.where(use, motif, base).astype(jnp.int32)}
    raise ValueError(f"unknown input kind {inputs['kind']!r}")


class Dataset:
    """A fixed dataset, imbalanced by keeping every positive and only as
    many negatives as the mix's positive share allows, and split evenly over
    ``n_workers`` IID shards; worker k draws only from shard k.

    The program's generator drops each negative at random; here the counts
    are fixed (half of ``n_data`` positives), so that every seed draws
    arrays of the same sizes and runs the same programs."""

    def __init__(self, seed: int, inputs: dict, traffic: dict, n_workers: int):
        if traffic.get("shards", "iid") != "iid":
            raise ValueError(f"shards {traffic['shards']!r}: only 'iid' "
                             "shards are generated")
        p = traffic["p_pos"]
        n_pos = traffic["n_data"] // 2
        n = n_pos + round(n_pos * (1 - p) / p)
        rng = numpy_rng(seed, 2)
        labels = np.zeros(n, np.float32)
        labels[rng.permutation(n)[:n_pos]] = 1.0
        self.inputs = {k: np.asarray(v) for k, v in _draw(
            jax_key(seed, 1), inputs, traffic, jnp.asarray(labels)).items()}
        self.labels = labels
        self.n = n
        per = n // n_workers
        self.shards = rng.permutation(n)[:per * n_workers].reshape(
            n_workers, per)


class Sampler:
    """``sample_window(key, I)`` for ``coda.fit``: [I, K, B, ...] batches,
    placed with ``sharding``.  The window's indices come from the sampler's
    own generator, so the same seed gives the same batches whatever key
    ``fit`` passes."""

    def __init__(self, ds: Dataset, batch: int, seed: int, sharding):
        self.ds, self.batch, self.sharding = ds, batch, sharding
        self.rng = numpy_rng(seed, 3)

    def host_window(self, I: int) -> dict:
        K, per = self.ds.shards.shape
        pick = self.rng.integers(0, per, size=(I, K, self.batch))
        idx = self.ds.shards[np.arange(K)[None, :, None], pick]
        out = {k: v[idx] for k, v in self.ds.inputs.items()}
        out["labels"] = self.ds.labels[idx]
        return out

    def place(self, host: dict) -> dict:
        out = jax.device_put(host, self.sharding)
        return jax.block_until_ready(out)

    def alpha_batch(self, key, m: int) -> dict:
        K, per = self.ds.shards.shape
        idx = self.ds.shards[np.arange(K)[:, None],
                             self.rng.integers(0, per, size=(K, m))]
        out = {k: v[idx] for k, v in self.ds.inputs.items()}
        out["labels"] = self.ds.labels[idx]
        return jax.device_put(out)
