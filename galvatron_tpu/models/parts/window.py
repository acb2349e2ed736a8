"""Softmax attention over a window of the last keys, a token mixer with its
own head count and rope among layers of full attention (Laguna's three
layers in four; HF's "sliding_attention")."""

from __future__ import annotations

import jax

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.attention import ATTENTION, attention_mixer
from galvatron_tpu.models.parts.common import LayerPart, Params, no_form
from galvatron_tpu.obs import tracing


def _validate(cfg: TransformerConfig) -> None:
    if cfg.sliding_window < 1 or not cfg.causal or cfg.latent_attention:
        raise ValueError("window layers want sliding_window, the keys a query sees up to its own, of 1 or more, "
                         "on causal attention that is not latent; got sliding_window=%d causal=%s kv_lora_rank=%d"
                         % (cfg.sliding_window, cfg.causal, cfg.kv_lora_rank))


# the decode engine's cache holds every key of a slot and has no window of
# the last ones; the ring passes whole blocks of keys and has no band; heads
# over tp would split the window kernels' key heads, which no chip run has
# shown; the cost models have no row
UNSUPPORTED = no_form(
    "window attention layers",
    serve="no cache of a window attention layer's last keys (serve/kv_cache.py holds every key of a slot)",
    autotune="a window attention layer as full attention",
    pp="stack one kind of layer a stage, not window attention layers among full attention layers",
    tp="window attention layers (the ring has no band, and the window kernels' heads have not been split "
       "over tensor-parallel ranks)",
)


def window_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, **how):
    """Softmax attention in which query i sees the keys `i - sliding_window <
    j <= i`, on normed activations (B, S, H): the attention part's projections,
    rope, gate and output projection on THIS layer's config (`layer_config`
    has made the window layers' heads, rope base and rotary share the ordinary
    fields), under `gt.attn.window`; the call itself is
    `ops/attention.core_attention(window=)`, under `gt.attn.band`. -> out, the
    post-rope (k, v) where asked, None."""
    return attention_mixer(p, y, positions, cfg, scope=tracing.ATTN_WINDOW, window=cfg.sliding_window, **how)


# (a window layer publishes nothing: a cross layer reads a FULL layer's keys and values)
WINDOW = LayerPart(ATTENTION.init, window_mixer, ATTENTION.specs,
                   (tracing.ATTN_WINDOW, tracing.ATTN_WINDOW_BAND, tracing.ATTN_DIFF),
                   validate=_validate, unsupported=lambda cfg: UNSUPPORTED, place=ATTENTION.place)
