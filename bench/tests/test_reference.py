"""The reference's block merge and the control's half batch keep every
node's sampled neighbours: logits of a merged block are the logits of its
parts."""
import jax
import numpy as np
import pytest

from bench import control, flops, reference

FAN = (3, 2)


def _block(rng, batch, n=500):
    sizes = flops.frontier_sizes(batch, FAN)
    hops = [rng.integers(0, n, sizes[i] * f) for i, f in enumerate(FAN)]
    return rng.integers(0, n, batch), hops


def _logits(model, params, x, deg, targets, hops):
    ids, d = reference.block_arrays(targets, hops, deg)
    return np.asarray(reference.forward(params, model, FAN, x[ids], d,
                                        np.float32))


@pytest.mark.parametrize("model", ["sage", "gcn"])
def test_merged_block_gives_the_parts_logits(model):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((500, 8)).astype(np.float32)
    deg = rng.integers(1, 40, 500)
    params = reference.init_params(jax.random.PRNGKey(1), model, (8, 16, 4))
    a, b = _block(rng, 2), _block(rng, 3)
    t, h = reference.merge_blocks([a, b], FAN)
    merged = _logits(model, params, x, deg, t, h)
    parts = np.concatenate([_logits(model, params, x, deg, *a),
                            _logits(model, params, x, deg, *b)])
    np.testing.assert_allclose(merged, parts, rtol=1e-5, atol=1e-6)
    half = control.half_block({"targets": b[0], "hop_src": b[1],
                               "share": 3}, FAN)
    np.testing.assert_allclose(
        _logits(model, params, x, deg, half["targets"], half["hop_src"]),
        parts[2:3], rtol=1e-5, atol=1e-6)
