"""Decode-path equivalence: the decomposed (old-cache ⊕ new-token) attention
must match the write-then-attend baseline exactly, incl. SWA ring wrap."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.distributed.sharding import decode_rules, use_rules
from repro.models import build_model


def _mesh11():
    from repro.launch.mesh import make_test_mesh

    return make_test_mesh((1, 1))


@pytest.mark.parametrize("arch", ["qwen3-32b", "mixtral-8x7b", "qwen2-72b"])
def test_decomposed_decode_matches_masked(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    b, prompt, steps = 2, 6, 40  # 40 steps: wraps mixtral's window=32 ring
    toks = rng.integers(0, cfg.vocab_size, (b, prompt + steps), dtype=np.int32)
    total = prompt + steps

    rules_dec = dataclasses.replace(decode_rules(_mesh11()), cache_impl="decomposed")

    def run(decomposed: bool):
        cache = m.init_cache(b, total, dtype=jnp.float32)
        logits, cache = m.prefill(
            params, {"tokens": jnp.asarray(toks[:, :prompt])}, cache
        )
        outs = [logits]
        for t in range(prompt, total):
            tok = jnp.asarray(toks[:, t : t + 1], jnp.int32)
            if decomposed:
                with use_rules(rules_dec):
                    logits, cache = m.decode_step(
                        params, cache, tok, jnp.asarray(t, jnp.int32)
                    )
            else:
                logits, cache = m.decode_step(
                    params, cache, tok, jnp.asarray(t, jnp.int32)
                )
            outs.append(logits)
        return np.stack([np.asarray(o) for o in outs], 1)

    base = run(False)
    dec = run(True)
    np.testing.assert_allclose(dec, base, rtol=2e-5, atol=2e-5)
