"""The comparison that decides ``correct``: served tokens against the
plain reference.

Once the window has closed, a sample of the requests the window
finished is drawn from the seed, with the longest of them always in it.
The reference runs once over each sampled prompt followed by its served
tokens.  At each position that produced a served token it reads how far
that token's logit lies below the reference's best logit there (the
token's gap), and at the prompt's last position it compares the logits
the program sampled the first token from with its own (their RMS
deviation, relative to the reference's RMS logit).

The readings over the sample are the widest gap, the mean gap, the
share of served tokens that were not the reference's best, and the
largest first-token deviation.  Which of them a cell compares, and
against what limit, its cell file says (``check.limits``).
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_reference(name: str):
    path = os.path.join(BENCH_DIR, "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(finished: list, n: int, seed: int) -> list:
    """``n`` of ``finished`` drawn from ``seed``, the longest (prompt plus
    output) always among them."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i].prompt)
                  + len(finished[i].output))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([seed, 7])
    take = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [finished[longest]] + [finished[rest[i]] for i in sorted(take)]


def served_gaps(params, config: dict, reqs: list, *, seq_len: int,
                rows: int) -> tuple[list[np.ndarray], list[float]]:
    """Per request, the gap of each served token below the reference's
    best logit at the position that produced it, and the RMS deviation
    of the request's first-token logits from the reference's.  Sequences
    are padded to ``seq_len`` and positions to ``rows``, so every request
    runs the same compiled program."""
    import jax
    import jax.numpy as jnp

    ref = load_reference(config["reference"])
    vocab = config["vocab_size"]

    @jax.jit
    def gaps(params, tokens, pos, served, first):
        lg = ref.logits_at(params, config, tokens, pos)
        got = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
        rms = lambda x: jnp.sqrt(jnp.mean(jnp.square(x)))
        dev = rms(first[:vocab] - lg[0]) / rms(lg[0])
        return jnp.max(lg, axis=1) - got, dev

    out, devs = [], []
    for r in reqs:
        seq = list(r.prompt) + list(r.output[:-1])
        n = len(r.output)
        if len(seq) > seq_len or n > rows:
            raise ValueError(f"request {r.uid}: {len(seq)} positions, "
                             f"{n} served tokens exceed {seq_len}/{rows}")
        tokens = np.zeros((seq_len,), np.int32)
        tokens[:len(seq)] = seq
        pos = np.full((rows,), len(r.prompt) - 1, np.int32)
        pos[:n] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + n)
        served = np.zeros((rows,), np.int32)
        served[:n] = r.output
        g, dev = gaps(params, jnp.asarray(tokens), jnp.asarray(pos),
                      jnp.asarray(served),
                      jnp.asarray(r.first_logits, jnp.float32))
        out.append(np.asarray(g)[:n])
        devs.append(float(dev))
    return out, devs


def readings(gaps: list[np.ndarray], devs: list[float]) -> dict:
    """The numbers a cell can compare, over one run's sample."""
    g = np.concatenate(gaps)
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean()),
            "off_best_share": float((g > 0).mean()),
            "first_logit_dev": float(max(devs)), "tokens": int(g.size)}
