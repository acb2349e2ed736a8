"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
a Laguna and a Kimi-Linear routed block's megablox calls at the tilings their shapes take (PR 69: `ops/moe.gmm_tiling`),
and a Nemotron-H block's, whose experts' width no multiple of 128 divides (PR 71: 1856 as ONE block)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import attention as A
from galvatron_tpu.ops import moe
from tests.ops.tpu_compile import v5e_2x2  # noqa: F401  (the fixture)

# the cells laguna-c1-s8k and kimilin-c1-s8k, and OLMoE's block as `scripts/olmoe_chip_check.py` runs it (4096 tokens:
# 512 rows a group, all experts held, no window): tokens, hidden, an expert's width, experts, held, experts a token, the router
BLOCKS = {
    "laguna": dict(tokens=8192, hidden=2048, ffn=512, experts=256, held=32, k=8,
                   router=dict(score="softmax", norm_topk_prob=True)),
    "kimi": dict(tokens=8192, hidden=2304, ffn=1024, experts=256, held=8, k=8,
                 router=dict(score="sigmoid", norm_topk_prob=True, scale=2.446)),
    "olmoe_4k": dict(tokens=4096, hidden=2048, ffn=1024, experts=64, held=None, k=8,
                     router=dict(score="softmax", norm_topk_prob=False)),
    # the cell nemo3n-c1-s8k: two matrices an expert (`gate` False: the up projection is as wide as the expert), squared ReLU
    "nemo3n": dict(tokens=8192, hidden=2688, ffn=1856, experts=128, held=8, k=6, gate=False,
                   router=dict(score="sigmoid", norm_topk_prob=True, scale=2.5)),
}


def _up_width(b):
    return b["ffn"] * (2 if b.get("gate", True) else 1)


def _pallas_calls(jaxpr, inside=""):
    """(the jitted function it stands in, the equation) of every `pallas_call` of a jaxpr, through whatever holds a
    jaxpr of its own (a `cond`'s branches, a jitted rule, a custom rule's body). megablox's kernels carry no name of
    their own: `gmm` and `tgmm` are the jits around them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield inside, eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner, eqn.params.get("name", inside) if eqn.primitive.name == "jit" else inside)


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def block(request, v5e_2x2):
    """(the block's numbers, forward + backward compiled for one described chip as text, its jaxpr, what `forms` heard)"""
    b = BLOCKS[request.param]
    one = SingleDeviceSharding(v5e_2x2[0])
    on_chip = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))
    sigmoid = b["router"]["score"] == "sigmoid"

    def loss(y, router, wi, wo, *bias):
        out, _ = moe.moe_ffn(y, router, wi, wo, experts_per_token=b["k"], dtype=y.dtype, sharding=on_chip,
                             bias=bias[0] if bias else None, held=b["held"] and (0, b["held"]),
                             activate=moe.swiglu if b.get("gate", True) else lambda mid: jnp.square(jax.nn.relu(mid)),
                             **b["router"])
        return jnp.sum(out.astype(jnp.float32) ** 2)

    f32 = jnp.float32
    count = b["held"] or b["experts"]
    operands = (jax.ShapeDtypeStruct((1, b["tokens"], b["hidden"]), jnp.bfloat16, sharding=one),
                jax.ShapeDtypeStruct((b["hidden"], b["experts"]), f32, sharding=one),
                jax.ShapeDtypeStruct((count, b["hidden"], _up_width(b)), f32, sharding=one),
                jax.ShapeDtypeStruct((count, b["ffn"], b["hidden"]), f32, sharding=one))
    if sigmoid:
        operands += (jax.ShapeDtypeStruct((b["experts"],), f32, sharding=one),)
    grad = jax.grad(loss, argnums=(0, 1, 2, 3))
    with forms.recording() as took:
        text = jax.jit(grad).lower(*operands).compile().as_text()
    return b, text, jax.make_jaxpr(grad)(*operands).jaxpr, took


def test_a_blocks_megablox_calls_compile_at_the_tiles_their_shapes_take(block):
    """The three kernels of both matmuls, in the window's branch and in the whole range's (or, with all experts held,
    through `grouped_matmul`'s own rule: megablox's would hand the forward's tiling to all three, and `tgmm` at OLMoE's
    (256, 1024, 2048) asks for 19.5 MiB of scoped VMEM), at (256 | 128, tk, tn) with tk and tn whole divisors of the
    dims they run over: the chip's compiler takes every one (the scoped VMEM among what it checks), and no operand or
    result block of any is wider than its array, so megablox traces no mask of a rest and multiplies no padding."""
    b, text, jaxpr, took = block
    assert "ragged-dot" not in text
    calls = [eqn for inside, eqn in _pallas_calls(jaxpr) if inside in ("gmm", "tgmm")]
    assert len(calls) >= (16 if b["held"] else 6)  # 2 + 6 a branch of each direction's `cond`; 2 + 4 without a window
    seen = set()
    for eqn in calls:
        for spec in eqn.params["grid_mapping"].block_mappings:
            held = tuple(d.block_size for d in spec.block_shape if hasattr(d, "block_size"))
            array = spec.array_aval.shape[-len(held):]
            assert len(held) == 2 and all(dim % tile == 0 for tile, dim in zip(held, array)), (held, array)
            seen.add(held)
    even = b["tokens"] * b["k"] / b["experts"]
    assert even <= 512 and moe.row_tile(even) == 256
    wide, h, f = _up_width(b), b["hidden"], b["ffn"]
    tilings = {moe.gmm_tiling(kernel, *dims, even) for k, n in ((h, wide), (f, h)) for kernel, dims in moe.matmul_calls(k, n)}
    assert {tm for tm, _, _ in tilings} == {256, 128}
    for tm, tk, tn in tilings:  # each chosen tiling's lhs block is among the blocks the calls hold
        assert (tm, tk) in seen, ((tm, tk, tn), sorted(seen))
    assert len(took[forms.GMM_TILES]) == 6 and set(took[forms.GMM_TILES].values()) == {1}
    window = moe.window_rows(b["tokens"] * b["k"], b["experts"], b["held"] and (0, b["held"]))
    assert took[forms.EXPERT_WINDOW] == ({str(window): 1} if b["held"] else {})


def test_the_benchmarks_readers_find_the_calls_under_the_experts_scopes(block):
    """What `*_moe_held_gmm_roofline` reads: every `gmm` / `tgmm` custom call of the compiled block carries
    `gt.moe.experts/gmm_in` or `/gmm_out` as `benchmarks/layer_metrics/moe_gmm_roofline` spells them (`KERNEL`,
    `KINDS`), whatever tiling the call took: 8 each over the two directions' two branches, and the up projection's
    third forward in the whole range's backward (`_windowed_bwd`'s `again`)."""
    from benchmarks import trace
    from benchmarks.layer_metrics import moe_gmm_roofline

    b, text, _, _ = block
    origins = trace.origins_from_hlo(text)
    calls = [line.split("=")[0].strip().lstrip("%").removeprefix("ROOT %") for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    labels = [label for label in (trace._label(name, origins) for name in calls) if re.search(moe_gmm_roofline.KERNEL, label)]
    found = {kind: [label for label in labels if re.search(scope, label)] for kind, scope in moe_gmm_roofline.KINDS.items()}
    assert (len(found["in"]), len(found["out"]), len(labels)) == ((9, 8, 17) if b["held"] else (3, 3, 6)), labels
