"""The experts' grouped product (`ops/grouped_matmul.py`): the kernel that
reads a bank where it lies in a stack of banks, in interpret mode here,
against `lax.ragged_dot` on the bank's slice, which is what runs off a TPU
and what ran everywhere before ISSUE 37; and `DroplessMoE` under a scanned
stack of two periods, handed the whole stack, against the scan's own
slice.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference
from pytorchdistributed_tpu.models.moe import DROPLESS_COUNTERS
from pytorchdistributed_tpu.ops import grouped_matmul
from tests.test_latent_serving import LogitSpy, serve
from tests.test_smallthinker_serving import TOY, make_engine

ROWS = 200
#: the rows a group of the four named, of 200 (the rest belong to nobody)
SIZES = {
    "spread": (70, 50, 30, 50),
    "groups_of_no_rows": (0, 120, 0, 80),
    "one_group_has_all": (0, 0, 200, 0),
    "rows_past_the_last_group": (3, 0, 20, 5),     # dots3's: most unheld
    "a_few_rows_a_group": (2, 3, 1, 3),            # a tick's
    "no_rows_at_all": (0, 0, 0, 0),
}


@pytest.mark.parametrize("first_group", [0, 8])
@pytest.mark.parametrize("k,n", [(256, 128), (128, 256)],
                         ids=["d_to_f", "f_to_d"])
@pytest.mark.parametrize("case", SIZES)
def test_kernel_matches_ragged_dot_on_the_banks_slice(case, k, n,
                                                      first_group):
    """Group ``i`` multiplies by ``bank[first_group + i]`` of a bank of
    twelve, whatever the groups' sizes; rows past the last group come
    back nought, as `lax.ragged_dot` leaves them; bf16 operands, float32
    sums in another order."""
    keys = jax.random.split(jax.random.key(37), 2)
    lhs = jax.random.normal(keys[0], (ROWS, k), jnp.bfloat16)
    bank = jax.random.normal(keys[1], (12, k, n), jnp.bfloat16)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    got = jax.jit(functools.partial(
        grouped_matmul.kernel_product, interpret=True))(
            lhs, bank, sizes, first_group)
    want = grouped_matmul.sliced_product(lhs, bank, sizes, first_group)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)   # of ~50
    total = sum(SIZES[case])
    assert not np.asarray(got[total:]).any()
    if total:
        own = lhs[:SIZES[case][0]].astype(jnp.float32) @ bank[
            first_group].astype(jnp.float32)
        np.testing.assert_allclose(got[:SIZES[case][0]], own, atol=2e-4)


@pytest.mark.parametrize("seed", range(4))
def test_schedule_visits_each_tile_a_group_has_rows_in(seed):
    """The grid's steps, against the rows counted one by one: a step for
    every (group, row tile) pair that shares a row and no other, groups
    in order and tiles never falling, so a tile's visits are consecutive;
    with rows left over, groups of no rows and groups that end on a
    tile's edge."""
    rng = np.random.default_rng(seed)
    g, tm, tiles_m = 12, 8, 10
    sizes = rng.multinomial(rng.integers(0, tm * tiles_m + 1),
                            rng.dirichlet(np.full(g, 0.3))).astype(np.int32)
    if seed == 0:
        sizes = np.asarray([8, 0, 16, 3, 5, 0, 0, 8, 1, 0, 7, 0], np.int32)
    offsets, group_ids, tile_ids, steps = jax.jit(
        grouped_matmul._schedule, static_argnums=(1, 2))(
            jnp.asarray(sizes), tiles_m, tm)
    group_of_row = np.repeat(np.arange(g), sizes)
    want = sorted({(int(grp), r // tm) for r, grp in enumerate(group_of_row)})
    steps = int(steps)
    got = list(zip(np.asarray(group_ids)[:steps].tolist(),
                   np.asarray(tile_ids)[:steps].tolist()))
    assert got == want and steps <= tiles_m + g - 1
    assert np.asarray(offsets).tolist() == [0, *np.cumsum(sizes)]
    assert (np.asarray(group_ids) < g).all() and (
        np.asarray(tile_ids) < tiles_m).all()


def test_tiles_follow_the_shapes_a_call_sees():
    """The whole of ``k`` and the widest ``n`` whose block of the bank
    fits, whole 128-lane columns that divide ``n``; more rows a tile
    where a call has many. No tile is larger than what it tiles."""
    tiling = grouped_matmul.tiling
    for m, k, n in [(192, 2560, 768), (3072, 768, 2560),
                    (256, 5120, 1536), (8192, 1536, 5120), (48, 64, 32)]:
        tm, tk, tn = tiling(m, k, n, 2)
        assert k % tk == 0 and n % tn == 0 and tm % 8 == 0
        assert (tk * tn * 2 <= grouped_matmul.BANK_BLOCK_BYTES
                or (tk, tn) == (k, n))
    assert tiling(192, 2560, 768, 2)[0] < tiling(3072, 2560, 768, 2)[0]


def test_scanned_stack_hands_the_kernel_its_banks_whole():
    """SmallThinker's toy (two periods of four layers, eight experts a
    layer) through the engine, chunks and ticks: with the kernel in
    `grouped_product`'s place (interpreted) every product is handed the
    stack's `[2 x 8, ...]` leaf and the period's first group, and the
    logits are those of `lax.ragged_dot` on the slice within float32
    rounding, the six counters equal. (The banks in the compute type, as
    the engine holds a cell's: a bank in another type keeps the scan's
    slice, which every other test of this toy runs.)"""
    fam = manifest.load_family(manifest.BENCH_DIR, "smallthinker")
    toy = dict(TOY, param_dtype="float32")
    w = jax.jit(lambda s: fam.make_weights(toy, s))(
        reference.seed_u32(2 ** 31 + 37))
    seen = []

    def kernel(lhs, bank, sizes, first_group=0):
        seen.append((bank.shape[0], sizes.shape[0]))
        return grouped_matmul.kernel_product(lhs, bank, sizes, first_group,
                                             interpret=True)

    runs = {}
    # a context length each, so that the second engine's programs are
    # traced anew and not found in the jit's cache
    for name, positions in (("sliced", 128), ("in_place", 112)):
        with pytest.MonkeyPatch.context() as patch:
            if name == "in_place":
                patch.setattr(grouped_matmul, "grouped_product", kernel)
            eng = make_engine(fam, w, dict(toy, served_positions=positions))
            spy = LogitSpy(eng, patch)
            reqs = serve(eng, [(40, 12), (9, 20)], TOY["vocab_size"],
                         seed=37)
            summary = eng.summary()
            runs[name] = ([spy.logits[r.id] for r in reqs],
                          [r.new_tokens for r in reqs],
                          [summary[c] for c in DROPLESS_COUNTERS])
            eng.close()
    # a period's twelve products, of the chunk and of the tick; where the
    # engine initialises the model for its cache's shapes there are no
    # parameters yet to hand, and a layer multiplies by its own
    assert seen.count((2 * 8, 8)) >= 2 * 12
    assert set(seen) <= {(2 * 8, 8), (8, 8)}
    (want, tokens, counted), (got, tokens_k, counted_k) = (
        runs["sliced"], runs["in_place"])
    assert tokens == tokens_k and counted == counted_k
    assert counted[DROPLESS_COUNTERS.index("moe_dropped")] == 0
    for rows, rows_k in zip(want, got):
        assert sorted(rows) == sorted(rows_k)
        for position, row in rows.items():
            np.testing.assert_allclose(rows_k[position], row, rtol=0,
                                       atol=1e-5 * np.abs(row).max())
