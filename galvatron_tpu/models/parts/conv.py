"""The gated short convolution, a token mixer that is two matmuls around a
memory-bound elementwise pass (LFM2's three layers in four)."""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense, _dense_init, _proj_std, no_form
from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.ops import linear_attention
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes


def _validate(cfg: TransformerConfig) -> None:
    if cfg.short_conv_kernel < 1:
        raise ValueError("short-convolution layers want short_conv_kernel, the taps of their causal "
                         "depthwise convolution, of 1 or more; got %d" % cfg.short_conv_kernel)


# a rank's channels would want the three chunks of `win` split alike, a split
# sequence a halo of taps - 1 tokens between ranks, the decode engine the
# window of the last taps - 1 tokens; the cost models have no row
UNSUPPORTED = no_form(
    "short-convolution layers",
    serve="no window of a short-convolution layer's last tokens (serve/kv_cache.py holds keys and values)",
    autotune="a short-convolution layer as softmax attention",
    pp="stack one kind of layer a stage, not short-convolution layers among attention layers",
    tp="short-convolution layers (a rank's channels want the input projection's three chunks split alike, "
       "and a split sequence a halo of the taps' reach between ranks)",
)


def _init_conv(ks, cfg: TransformerConfig) -> Params:
    """The short-convolution mixer's leaves, under `conv` (HF
    `Lfm2MoeShortConv`: in_proj, conv, out_proj). `win` is ONE kernel whose
    output columns lie [B | C | u], three chunks of `hidden_size` channels, as
    HF's `in_proj(x).chunk(3, dim=-1)` cuts them; the taps (channels, K) with
    tap K - 1 on the current token, U(-1, 1) / sqrt(K): PyTorch's default for a
    depthwise Conv1d of that fan-in; no bias anywhere."""
    h, taps = cfg.hidden_size, cfg.short_conv_kernel
    return {"conv": {
        "win": {"kernel": _dense_init(ks[0], (h, 3 * h), cfg.init_std, cfg.param_dtype)},
        "taps": jax.random.uniform(ks[4], (h, taps), cfg.param_dtype, -1.0, 1.0) / taps ** 0.5,
        "wout": {"kernel": _dense_init(ks[1], (h, h), _proj_std(cfg), cfg.param_dtype)},
    }}


def conv_mixer(p: Params, y: jax.Array, positions, cfg: TransformerConfig, **_):
    """The gated short convolution on normed activations (B, S, H) (HF
    `Lfm2MoeShortConv`), p the layer's tree:

        [B | C | u] = y Win
        v_t = sum_j taps[:, j] (B * u)_{t - (K - 1) + j}    causal, depthwise, zeros before the
                                                            sequence, no activation, no bias
        out = (C * v) Wout

    `B * u` and `C * v` in the compute dtype, the taps' sum in float32
    (`causal_conv`: one pad, then K slices). -> out, None, None. Scopes: the
    two matmuls under `gt.attn.shortconv`, the pass between them under
    `gt.attn.conv_gate`. No position enters: the order is the convolution's."""
    p, dtype, h = p["conv"], cfg.compute_dtype, cfg.hidden_size
    forms.took(forms.SHORT_CONV, "xla")
    with jax.named_scope(tracing.ATTN_CONV_PROJ):
        bcu = _dense(y, p["win"], dtype)
    with jax.named_scope(tracing.ATTN_CONV_GATE):
        v = linear_attention.causal_conv(bcu[..., :h] * bcu[..., 2 * h:], p["taps"])
        gated = bcu[..., h:2 * h] * v
    with jax.named_scope(tracing.ATTN_CONV_PROJ):
        return _dense(gated, p["wout"], dtype), None, None


def _conv_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves (tp, sp, cp are refused, GLS018): ZeRO-3 splits the
    # projections' input dim; the taps are whole everywhere
    z3 = S._ax(axes.dp) if axes.zero3 else None
    return {"conv": {"win": {"kernel": P(z3, None)}, "taps": P(None, None), "wout": {"kernel": P(z3, None)}}}


CONV = LayerPart(_init_conv, conv_mixer, _conv_specs, (tracing.ATTN_CONV_PROJ, tracing.ATTN_CONV_GATE),
                 validate=_validate, unsupported=lambda cfg: UNSUPPORTED)
