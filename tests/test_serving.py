"""Serving engine: continuous batching, slot reuse, greedy correctness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import LRDConfig, ParallelConfig, RunConfig
from repro.models.api import get_model
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get("llama3.2-1b").smoke
    run = RunConfig(model=cfg, parallel=ParallelConfig())
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    return run, m, params


def _greedy_reference(m, params, prompt, n, max_seq):
    """Reference greedy decode via repeated full forward."""
    toks = list(prompt)
    for _ in range(n):
        x, _ = m.forward(params, {"tokens": jnp.asarray([toks])})
        logits = m.logits(params, x)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


class TestServeEngine:
    def test_outputs_match_reference_exactly(self, setup):
        run, m, params = setup
        eng = ServeEngine(run, params, slots=2, max_seq=64)
        prompt = [5, 9, 2, 7]
        req = Request(uid=0, prompt=prompt, max_new_tokens=6)
        eng.add_request(req)
        eng.run_until_done()
        assert req.done and len(req.output) == 6
        ref = _greedy_reference(m, params, prompt, 6, 64)
        assert req.output == ref

    def test_continuous_batching_slot_reuse(self, setup):
        run, m, params = setup
        eng = ServeEngine(run, params, slots=2, max_seq=64)
        reqs = [Request(uid=i, prompt=[i + 1, i + 2, i + 3],
                        max_new_tokens=3 + i % 3) for i in range(5)]
        for r in reqs:
            eng.add_request(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        for r in reqs:
            assert len(r.output) == r.max_new_tokens
        # batching actually happened (2 slots, 5 requests)
        assert max(s["live"] for s in eng.stats) == 2
        assert eng.throughput()["tokens_per_s"] > 0

    def test_batched_outputs_equal_isolated(self, setup):
        """Slot interference check: results identical whether a request
        runs alone or batched with others."""
        run, m, params = setup
        prompts = [[3, 1, 4], [1, 5, 9, 2], [6, 5]]
        solo = []
        for i, p in enumerate(prompts):
            eng = ServeEngine(run, params, slots=1, max_seq=64)
            r = Request(uid=i, prompt=p, max_new_tokens=5)
            eng.add_request(r)
            eng.run_until_done()
            solo.append(r.output)
        eng = ServeEngine(run, params, slots=3, max_seq=64)
        batched = [Request(uid=i, prompt=p, max_new_tokens=5)
                   for i, p in enumerate(prompts)]
        for r in batched:
            eng.add_request(r)
        eng.run_until_done()
        for s, b in zip(solo, batched):
            assert s == b.output

    def test_decomposed_model_serves(self, setup):
        """LRD-compressed params serve through the same engine."""
        run, m, params = setup
        from repro.core.surgery import decompose_model
        _, axes = m.init(jax.random.PRNGKey(0))
        lrd = LRDConfig(enabled=True, rank_mode="ratio", min_dim=32)
        p2, _, _ = decompose_model(params, axes, lrd)
        run2 = dataclasses.replace(run, lrd=lrd)
        eng = ServeEngine(run2, p2, slots=2, max_seq=64)
        req = Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4)
        eng.add_request(req)
        eng.run_until_done()
        assert req.done and len(req.output) == 4

    @pytest.mark.parametrize("admission", ["continuous", "blocking"])
    def test_keep_logits_holds_first_token_logits(self, setup, admission):
        """A request that asks keeps the logits its first token was
        sampled from; others keep nothing."""
        run, m, params = setup
        eng = ServeEngine(run, params, slots=2, max_seq=64,
                          admission=admission)
        kept = Request(uid=0, prompt=[5, 6, 7, 8], max_new_tokens=3,
                       keep_logits=True)
        plain = Request(uid=1, prompt=[5, 6, 7, 8], max_new_tokens=3)
        for r in (kept, plain):
            eng.add_request(r)
        eng.run_until_done()
        assert plain.first_logits is None
        logits = np.asarray(kept.first_logits)
        assert logits.shape == (m.padded_vocab,)
        assert int(np.argmax(logits)) == kept.output[0] == plain.output[0]
