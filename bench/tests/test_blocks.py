"""The reference quantizes in the blocks the configuration states: the same
cut of each leaf into blocks, with a norm of its own, that the program makes
(``core/gossip._scan_plan``), checked on the cells' own leaf shapes."""
import math

import jax
import numpy as np
import pytest

from repro.core.gossip import _scan_plan
from yardstick import adgda_ref, registry, weights


def _program_blocks(shape, limit):
    plan = _scan_plan((2,) + shape, math.prod(shape), limit)
    if plan is None:
        return None
    axis, chunks, _ = plan
    return (axis - 1, chunks)


@pytest.mark.parametrize("layers", [1, 4, 6, 7, 8, 12, 28])
def test_blocks_match_the_program(layers):
    conf = registry.load_cell("adgda-q17b-ring2-s512").config
    model = {**conf["model"], "num_hidden_layers": layers}
    limit = conf["train"]["quant_block_elems"]
    shapes = [tuple(s) for s, _ in weights.layout(model).values()] + [(300,), (3, 7, 5)]
    for shape in shapes:
        assert adgda_ref.blocks(shape, limit) == _program_blocks(shape, limit), shape


def test_blocks_quantize_with_a_norm_each():
    x = np.concatenate([np.full((2, 8), 1.0), np.full((2, 8), 100.0)]).astype(np.float32)
    q = np.asarray(adgda_ref.quantize_blocks(x, jax.random.PRNGKey(0), bits=4, plan=(0, 2)))
    # each block holds constant entries: its own norm sets the level, the same in each
    tau = adgda_ref.tau(16, 4)
    for blk, val in ((q[:2], 1.0), (q[2:], 100.0)):
        norm = val * 4.0
        lvl = np.floor(16 * val / norm)  # no noise needed: 16 x 1/4 = 4 exactly
        np.testing.assert_allclose(blk, lvl * norm / (16 * tau), rtol=1e-6)
