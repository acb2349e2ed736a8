"""The tiling of each megablox call is chosen from that call's shapes (PR 69: `ops/moe.gmm_tiling`): the rule over the
seven routed cells' shapes, against the chip sweep it was written from, and what it says to `obs/forms`; and (PR 71)
the rule for a dim that NO multiple of 128 divides, Nemotron-H's 1856-wide experts, against its own sweep."""

import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import moe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SWEEP = os.path.join(REPO, "chiprun_out", "moe_gmm_sweep.json")

# the routed block of each cell (benchmarks/configs/): tokens a step, experts a token, experts, held, hidden, the up
# projection's width (gate beside up), an expert's own
CELLS = {
    "olmoe-c1-s4k": (8192, 8, 64, None, 2048, 2048, 1024),
    "glm47f-c1-s8k": (8192, 4, 64, 8, 2048, 3072, 1536),
    "qwen3next-c1-s8k": (8192, 10, 512, 32, 2048, 1024, 512),
    "kimilin-c1-s8k": (8192, 8, 256, 8, 2304, 2048, 1024),
    "lfm2moe-c1-s8k": (16384, 4, 32, 8, 2048, 3584, 1792),
    "laguna-c1-s8k": (8192, 8, 256, 32, 2048, 1024, 512),
    "xing4-c1-s4k": (4096, 4, 64, 8, 3584, 2048, 1024),
}
SCOPED_VMEM = 16 << 20  # a v5e's, which a Mosaic kernel that asks for no more is held to
WINDOWS = {"glm47f-c1-s8k": 6656, "qwen3next-c1-s8k": 8192, "kimilin-c1-s8k": 3584, "lfm2moe-c1-s8k": 25088,
           "laguna-c1-s8k": 12800, "xing4-c1-s4k": 3584}  # as before PR 69


def _calls(cell):
    """(site, kernel, the dim its K tiles run over, the dim its N tiles run over) of a block's six kinds of call."""
    _, _, _, _, hidden, width, ffn = CELLS[cell]
    for site, (k, n) in (("in", (hidden, width)), ("out", (ffn, hidden))):
        for kernel, dims in moe.matmul_calls(k, n):
            yield site, kernel, dims


def _even_rows(cell):
    tokens, k, experts = CELLS[cell][:3]
    return tokens * k / experts


def _blocks_bytes(kernel, tiling):
    """By the kernels' own specs (jax's megablox/gmm.py): in and out blocks twice, the float32 scratch once."""
    tm, tk, tn = tiling
    blocks = ((tm, tk), (tm, tn), (tk, tn)) if kernel == "tgmm" else ((tm, tk), (tk, tn), (tm, tn))
    scratch = (tk, tn) if kernel == "tgmm" else (tm, tn)
    return sum(2 * 2 * rows * cols for rows, cols in blocks) + 4 * scratch[0] * scratch[1]


@pytest.mark.parametrize("cell,site,kernel", [(cell, site, kernel) for cell in CELLS for site, kernel, _ in _calls(cell)])
def test_a_calls_tiles_fit_its_shapes(cell, site, kernel):
    """No block is wider than its array and none leaves a rest to mask: tk and tn are multiples of 128 that divide
    the dims they run over; the row tile divides the rows the kernel is handed (the window, or all the assignments) and
    the 512-row tile on which the window starts; a grid step's blocks lie inside the scoped VMEM."""
    tokens, k, experts, held, _, _, _ = CELLS[cell]
    dims = {(s, kn): d for s, kn, d in _calls(cell)}[site, kernel]
    even = _even_rows(cell)
    tm, tk, tn = moe.gmm_tiling(kernel, *dims, even)
    assert tk % 128 == 0 and tn % 128 == 0 and dims[0] % tk == 0 and dims[1] % tn == 0
    rows = moe.window_rows(tokens * k, experts, held and (0, held)) or tokens * k
    assert rows % tm == 0 and moe.GMM_TILING[0] % moe.row_tile(even) == 0 and moe.row_tile(even) % tm == 0 and tm % 128 == 0
    assert _blocks_bytes(kernel, (tm, tk, tn)) == moe.gmm_blocks_bytes(kernel, (tm, tk, tn)) <= moe.GMM_VMEM < SCOPED_VMEM
    assert tm == (512 if even > 512 else 128 if kernel == "tgmm" else 256)


@pytest.mark.parametrize("site,kernel,dims", list(_calls("olmoe-c1-s4k")))
def test_olmoes_calls_keep_the_tiling_they_had(site, kernel, dims):
    """The routed code's control: 1024 rows a group, K and N that 1024 divides, and a whole K of 2048 beside 512-row
    tiles is 16 MiB of blocks, which the chip refused: (512, 1024, 1024), as every call took before PR 69."""
    assert moe.gmm_tiling(kernel, *dims, _even_rows("olmoe-c1-s4k")) == moe.GMM_TILING == (512, 1024, 1024)
    assert moe.gmm_blocks_bytes(kernel, (512, 2048, 1024)) >= SCOPED_VMEM


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_four_byte_operands_take_tiles_of_half_the_width_as_before(cell):
    """Float32 rows (the chip checks' float32 passes) were not timed: K and N tiles no wider than 512, that divide."""
    for _, kernel, dims in _calls(cell):
        tm, tk, tn = moe.gmm_tiling(kernel, *dims, _even_rows(cell), itemsize=4)
        assert tm == moe.row_tile(_even_rows(cell)) and tk <= 512 and tn <= 512 and dims[0] % tk == 0 and dims[1] % tn == 0
    if cell == "olmoe-c1-s4k":
        assert {moe.gmm_tiling(kernel, *dims, 1024.0, 4) for _, kernel, dims in _calls(cell)} == {(512, 512, 512)}


@pytest.mark.parametrize("cell", sorted(WINDOWS))
def test_a_shares_window_keeps_its_512_row_tiles_whatever_tile_its_calls_take(cell):
    """`WINDOW_OVER_EVEN` x the even share in 512-row tiles and a tile, as before PR 69: the step's arrays keep their
    lengths (at 256-row tiles Qwen3-Next's 7936 rows cost the combine's backward its place in VMEM: PERF.md, PR 69),
    and every call's row tile divides the window and its start."""
    tokens, k, experts, held, _, _, _ = CELLS[cell]
    rows, share = moe.window_rows(tokens * k, experts, (0, held)), tokens * k * held / experts
    assert rows == (math.ceil(moe.WINDOW_OVER_EVEN * share / 512) + 1) * 512 == WINDOWS[cell]
    assert {rows % moe.gmm_tiling(kernel, *dims, _even_rows(cell))[0] for _, kernel, dims in _calls(cell)} == {0}


@pytest.mark.skipif(not os.path.exists(SWEEP), reason="the chip sweep's record is not in this checkout")
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_picks_what_the_chip_sweep_timed(cell):
    """`chiprun_out/moe_gmm_sweep.json` (scripts/moe_gmm_sweep.py on a v5e, PR 69) is the rule's evidence: for every
    kernel of the cell the rule's tiling is one the chip compiled and ran, within 12 % of the fastest it timed at that
    row tile (at ANY row tile where groups hold at most 512 rows: above, the rule keeps 512-row tiles, with which
    OLMoE's calls are the parent's), and outside OLMoE's shapes faster than (512, 1024, 1024) was."""
    record = json.load(open(SWEEP))["cells"][cell]
    even = record["shapes"]["even_rows_a_group"]
    assert even == _even_rows(cell)
    for entry in record["kernels"]:
        dims = dict(moe.matmul_calls(entry["K"], entry["N"]))[entry["kernel"]]
        picked = list(moe.gmm_tiling(entry["kernel"], *dims, even))
        timed = {tuple(r["tiling"]): r["device_ms"] for r in entry["rows"] if r["groups"] == "uneven" and "device_ms" in r}
        assert tuple(picked) in timed, (entry["site"], entry["kernel"], picked)
        rivals = [ms for tiling, ms in timed.items() if even <= 512 or tiling[0] == picked[0]]
        assert timed[tuple(picked)] <= 1.12 * min(rivals), (entry["site"], entry["kernel"], picked)
        if cell != "olmoe-c1-s4k":
            assert timed[tuple(picked)] < 0.92 * timed[512, 1024, 1024], (entry["site"], entry["kernel"], picked)


def _block(tokens, k, experts, held, hidden, ffn, on_tpu):
    """A routed block traced, not run (the megablox kernels have no CPU lowering): what `forms` heard."""
    f32 = jnp.float32
    count = held[1] if held else experts
    operands = (jax.ShapeDtypeStruct((tokens, hidden), jnp.bfloat16), jax.ShapeDtypeStruct((hidden, experts), f32),
                jax.ShapeDtypeStruct((count, hidden, 2 * ffn), f32), jax.ShapeDtypeStruct((count, ffn, hidden), f32))

    def block(y, router, wi, wo):
        return moe._local_moe(y, router, None, wi, wo, k=k, norm_topk_prob=True, activate=moe.swiglu,
                              dtype=jnp.bfloat16, on_tpu=on_tpu, held=held)[0]

    with forms.recording() as took:
        jax.eval_shape(lambda *a: jax.grad(lambda *b: jnp.sum(block(*b).astype(f32)), argnums=(0, 2, 3))(*a), *operands)
        jax.eval_shape(block, *operands)  # a second block of the same shapes says nothing new
    return took


@pytest.mark.parametrize("held", [None, (4, 4)], ids=["all_held", "a_share"])
def test_forms_hears_the_tiling_of_each_distinct_call(held):
    """One entry a distinct (kernel, K x N, even rows a group) -> tiling, however many blocks and rules trace it, said
    where `_local_moe` sees the block take the megablox kernels; `cli report` prints the part as any other."""
    from galvatron_tpu.obs import report

    took = _block(2048, 2, 16, held, 512, 256, on_tpu=True)
    assert took[forms.GMM_TILES] == {
        "gmm 512x512 r256: 256x512x512": 1, "gmm_t 512x512 r256: 256x512x512": 1, "tgmm 512x512 r256: 128x512x512": 1,
        "gmm 256x512 r256: 256x256x512": 1, "gmm_t 512x256 r256: 256x512x256": 1, "tgmm 256x512 r256: 128x256x512": 1}
    assert took[forms.EXPERT_WINDOW] == ({} if held is None else {"2048": 2})  # (1.5 x 1024 in 512-row tiles) + a tile
    compile_event = {"v": 1, "t": 0.0, "seq": 0, "type": "compile", "trace_ms": 1.0, "compile_ms": 1.0, "forms": {p: dict(c) for p, c in took.items()}}
    printed = report.render(report.analyze([compile_event]))
    assert "gmm_tiles: " in printed and "tgmm 256x512 r256: 128x256x512 x 1" in printed


def test_forms_hears_no_tiling_where_the_kernels_do_not_run():
    """The CPU, and rows that are no whole row tiles, take `jax.lax.ragged_dot`: no tiling is chosen and none said."""
    assert forms.GMM_TILES not in _block(2048, 2, 16, None, 512, 256, on_tpu=False)
    assert forms.GMM_TILES not in _block(200, 2, 16, None, 512, 256, on_tpu=True)


# ------------------------------------------- a dim that no multiple of 128 divides (PR 71: nemo3n-c1-s8k)
NEMO = dict(tokens=8192, k=6, experts=128, held=8, hidden=2688, ffn=1856)  # two matrices an expert: the up projection is 1856 wide
NEMO_TILINGS = {("in", "gmm"): (256, 896, 1856), ("in", "gmm_t"): (256, 1856, 896), ("in", "tgmm"): (128, 896, 1856),
                ("out", "gmm"): (256, 1856, 896), ("out", "gmm_t"): (256, 896, 1856), ("out", "tgmm"): (128, 1856, 896)}


def _nemo_calls():
    for site, (k, n) in (("in", (NEMO["hidden"], NEMO["ffn"])), ("out", (NEMO["ffn"], NEMO["hidden"]))):
        for kernel, dims in moe.matmul_calls(k, n):
            yield site, kernel, dims


@pytest.mark.parametrize("site,kernel,dims", list(_nemo_calls()))
def test_a_dim_that_no_multiple_of_128_divides_is_one_block(site, kernel, dims):
    """1856 = 14.5 x 128 is taken WHOLE wherever a call's tiles run over it (a block as wide as its array: no mask,
    no padding multiplied), 2688 = 21 x 128 in tiles of 896 that divide it; the row tiles are the rule's (384 even
    rows a group: 256, `tgmm` 128) and divide the window; the blocks lie inside `GMM_VMEM`."""
    even = NEMO["tokens"] * NEMO["k"] / NEMO["experts"]
    tiling = moe.gmm_tiling(kernel, *dims, even)
    assert even == 384 and tiling == NEMO_TILINGS[site, kernel]
    for tile, dim in zip(tiling[1:], dims):
        assert dim % tile == 0 and (tile % 128 == 0 or tile == dim == 1856)
    assert moe.gmm_blocks_bytes(kernel, tiling) <= moe.GMM_VMEM
    window = moe.window_rows(NEMO["tokens"] * NEMO["k"], NEMO["experts"], (0, NEMO["held"]))
    assert window == 5120 and window % tiling[0] == 0
    # float32 operands (the chip check's float32 passes) keep tiles of half the width: 1856 is then wider than the
    # cap and stays masked, as every such dim did before this rule
    assert moe.gmm_tiling(kernel, *dims, even, itemsize=4)[1:] == tuple(384 if d == 2688 else 512 for d in dims)


def test_a_narrow_dim_that_128_does_not_divide_is_whole_too_and_one_too_wide_for_a_block_stays_masked():
    assert moe._tiles_of(200, 1024) == [200] and moe._fit(200, 1024) == 200
    assert moe._tiles_of(1856, 1024) == [] and moe._fit(1856, 1024) == 1024  # where `gmm_tiling` starts from
    assert moe.gmm_tiling("gmm", 2048, 4000, 384.0) == (256, 2048, 1024)  # wider than `GMM_WHOLE`: megablox masks the rest


@pytest.mark.skipif(not os.path.exists(SWEEP), reason="the chip sweep's record is not in this checkout")
def test_the_rule_for_1856_picks_what_its_chip_sweep_timed():
    """`chiprun_out/moe_gmm_sweep.json`, `nemo3n-c1-s8k` (scripts/moe_gmm_sweep.py on a v5e, PR 71): every picked
    tiling compiled and ran; each is faster than the masked 1024-wide tile it replaces and than the parent's one
    tiling; a block's six kinds of call add up to under the same block padded to 1920 columns at ITS best tilings; and
    the whole is within 10 % of the best of every candidate (which holds 128-row tiles and a K of 2688 whole, past
    `GMM_VMEM`)."""
    cells = json.load(open(SWEEP))["cells"]
    record, padded = cells["nemo3n-c1-s8k"], cells["nemo3n-c1-s8k-pad1920"]
    assert record["shapes"]["even_rows_a_group"] == 384
    total = best = 0.0
    for entry in record["kernels"]:
        dims = dict(moe.matmul_calls(entry["K"], entry["N"]))[entry["kernel"]]
        picked = moe.gmm_tiling(entry["kernel"], *dims, 384.0)
        assert picked == NEMO_TILINGS[entry["site"], entry["kernel"]]
        timed = {tuple(r["tiling"]): r["device_ms"] for r in entry["rows"] if r["groups"] == "uneven" and "device_ms" in r}
        masked = tuple(1024 if tile == 1856 else tile for tile in picked)
        assert timed[picked] < 0.93 * timed[masked] and timed[picked] < 0.7 * timed[512, 1024, 1024], (entry["site"], entry["kernel"])
        total, best = total + timed[picked], best + min(timed.values())
    rival = sum(min(r["device_ms"] for r in e["rows"] if r["groups"] == "uneven" and "device_ms" in r) for e in padded["kernels"])
    assert total < rival and total < 1.10 * best
