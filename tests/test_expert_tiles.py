"""The grouped expert product's tiles (``ops/moe.py`` ``expert_tiles``):
what the rule gives at the five expert configurations' widths and at a
few odd ones, and the ``megablox`` kernel in interpret mode against
``ragged_dot`` under those tiles.

Shapes only for the rule; tiny float32 operands, seeded, on the CPU for
the parity.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.moe import (
    _grouped_dot,
    expert_layer_tiles,
    expert_tiles,
)

CONFIGS = pathlib.Path(__file__).resolve().parent.parent / "chipbench/configs"
# The five configurations that serve routed experts (hidden, expert
# width), as chipbench/configs has them; the test below reads the files.
WIDTHS = {
    "qwen3-next-80b-a3b-ep4": (2048, 512),
    "longcat-flash-omni-ep32": (6144, 2048),
    "glm-4.7-flash-pp8": (2048, 1536),
    "lfm2-8b-a1b-ep4": (2048, 1792),
    "granite-4.0-h-small-ep4": (4096, 768),
}
PRODUCTS = [pytest.param(k, n, 2, id=f"{name}.{product}")
            for name, (h, f) in WIDTHS.items()
            for product, (k, n) in (("gate_up", (h, 2 * f)),
                                    ("down", (f, h)))]
ODD = [pytest.param(160, 256, 2, id="k160"),
       pytest.param(1000, 512, 2, id="k1000"),
       pytest.param(512, 192, 2, id="n192"),
       pytest.param(1792, 128, 4, id="float32.k1792"),
       pytest.param(6144, 4096, 4, id="float32.longcat.gate_up"),
       pytest.param(128, 128, 2, id="one-tile")]
SCOPED_VMEM = 16 << 20      # a v5e's default; the kernel asks for no more


def divisors(x):
    return [d for d in range(128, x + 1, 128) if x % d == 0]


def fits(tk, tn, itemsize):
    """One right-hand tile within its budget; two of them, two
    left-hand tiles, two output tiles and the accumulator (float32)
    beside each other within theirs, under the scoped VMEM."""
    buffers = 2 * (tk * tn + 128 * tk) * itemsize + 3 * 128 * tn * 4
    return (tk * tn * itemsize <= moe._RHS_TILE_BYTES
            and buffers <= moe._TILE_BUFFER_BYTES < SCOPED_VMEM)


def test_the_widths_are_the_cells():
    for name, widths in WIDTHS.items():
        hf = json.loads((CONFIGS / f"{name}.json").read_text())
        config = ModelConfig.from_hf_config(hf)
        assert (config.hidden_size, config.moe_intermediate_size) == widths
        assert config.num_experts > 0


@pytest.mark.parametrize("k,n,itemsize", PRODUCTS + ODD)
def test_the_tiles_follow_the_product(k, n, itemsize):
    tm, tk, tn = expert_tiles(k, n, itemsize)
    assert tm == 128
    # No remainder of k: the kernel's masked branch is never built.
    assert k % tk == 0
    if divisors(n):
        assert tn in divisors(n)
    else:
        assert tn == min(n, 512)
    assert fits(tk, tn, itemsize)
    # Nothing that fits takes fewer grid steps a visit, and nothing of
    # as few has a larger k tile.
    steps = (k // tk) * -(-n // tn)
    for other_k in [k] + divisors(k):
        for other_n in divisors(n):
            if fits(other_k, other_n, itemsize):
                assert (steps, -tk) <= ((k // other_k) * (n // other_n),
                                        -other_k)


@pytest.mark.parametrize("name", WIDTHS)
def test_a_visit_is_a_few_grid_steps(name):
    """Where the constant tile (128, 1024, 512) walked 8 to 72."""
    hidden, width = WIDTHS[name]
    was = sum(-(-k // 1024) * -(-n // 512)
              for k, n in ((hidden, 2 * width), (width, hidden)))
    layer = expert_layer_tiles(hidden, width, 2)
    assert layer["gate_up"] == list(expert_tiles(hidden, 2 * width, 2))
    assert layer["down"] == list(expert_tiles(width, hidden, 2))
    assert layer["steps_per_visit"] <= max(2, was // 3)


def test_a_k_without_a_fitting_divisor_keeps_the_constant_tile():
    narrowest = moe._RHS_TILE_BYTES // (128 * 2)
    k = narrowest + 8           # no multiple of 128 divides it
    assert expert_tiles(k, 512, 2) == (128, 1024, 512)
    assert k % 1024
    # One over a tile that has divisors takes one of them.
    _, tk, tn = expert_tiles(4 * narrowest, 512, 2)
    assert (4 * narrowest) % tk == 0 and tk % 128 == 0
    assert tk * tn * 2 == moe._RHS_TILE_BYTES
    # None of the five configurations is such a k.
    for hidden, width in WIDTHS.values():
        assert hidden <= narrowest and width <= narrowest


@pytest.mark.parametrize("k", [1792, 1536, 768])
def test_the_kernel_under_the_new_tiles_is_ragged_dot(k):
    """Empty groups, a group that straddles two m tiles, and rows past
    the groups' total (they come back zero from both)."""
    n, m = 256, 300
    sizes = jnp.array([0, 150, 0, 100, 30, 0], jnp.int32)
    assert int(sizes.sum()) < m and 128 < 150
    keys = jax.random.split(jax.random.PRNGKey(k), 2)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32)
    rhs = 0.05 * jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32)
    assert expert_tiles(k, n, 4)[1] == k
    got = _grouped_dot(lhs, rhs, sizes, "pallas-interpret")
    want = _grouped_dot(lhs, rhs, sizes, "xla")
    assert got.shape == want.shape == (m, n) and got.dtype == jnp.float32
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert not np.asarray(got[280:]).any()
    by_hand = np.asarray(jnp.dot(lhs[150:250], rhs[3],
                                 precision="highest"))
    assert np.abs(want[150:250] - by_hand).max() < 1e-4 * np.abs(
        by_hand).max()
