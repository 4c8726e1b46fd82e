"""Decoder-LM assembly for all architecture families.

Layer stacks are grouped into their minimal repeating *period* and scanned
with `jax.lax.scan` (small HLO even for 80-layer/1T-param programs — vital
for the CPU-hosted dry-run), with any remainder layers unrolled:

  * dense / MoE / VLM / enc-dec decoder: period 1
  * xlstm-1.3b: period 8 (7× mLSTM + 1× sLSTM)
  * zamba2-7b: period `shared_attn_every` with ONE weight-shared attention
    block applied at the start of each period (its KV caches are per-depth).

`forward` covers train / prefill / decode via the optional (cache,
cache_index) pair; MoE aux losses ride the scan carry.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.context import constrain

from .attention import (KV_SCOPE, _self_attention_math, attention,
                        init_attention, init_kv_cache)
from .config import (
    BLOCK_ATTN,
    BLOCK_MAMBA2,
    BLOCK_MLSTM,
    BLOCK_MOE,
    BLOCK_SLSTM,
    ModelConfig,
)
from .ffn import ffn, init_ffn
from .layers import (
    apply_linear,
    bf16_cotangent_barrier,
    dtype_of,
    embed,
    init_embedding,
    init_linear,
    init_rmsnorm,
    positions_for,
    rms_norm,
    rope_tables,
    unembed,
)
from .moe import init_moe, moe_ffn
from .ssm import init_mamba2, init_ssm_cache, mamba2_block
from .xlstm import (
    init_mlstm,
    init_mlstm_cache,
    init_slstm,
    init_slstm_cache,
    mlstm_block,
    slstm_block,
)


# ---------------------------------------------------------------- layout --
@dataclasses.dataclass(frozen=True)
class StackLayout:
    kinds: Tuple[str, ...]       # full layer pattern
    period: int
    n_full: int                  # scanned periods
    tail: Tuple[str, ...]        # unrolled remainder kinds
    shared_attn: bool

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        return self.kinds[: self.period]


def _minimal_period(pattern: Tuple[str, ...]) -> int:
    for p in range(1, len(pattern) + 1):
        if all(pattern[i] == pattern[i % p] for i in range(len(pattern))):
            return p
    return len(pattern)


def stack_layout(cfg: ModelConfig) -> StackLayout:
    pattern = cfg.layer_pattern()
    p = _minimal_period(pattern)
    if cfg.shared_attn_every:
        p = max(p, cfg.shared_attn_every)
    if not cfg.scan_layers:
        p = len(pattern)
    n_full = len(pattern) // p
    tail = pattern[n_full * p:]
    return StackLayout(pattern, p, n_full, tail, bool(cfg.shared_attn_every))


# ------------------------------------------------------------------ init --
def init_block(key, cfg: ModelConfig, kind: str, dtype, cross: bool = False) -> Dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    if kind in (BLOCK_ATTN, BLOCK_MOE):
        p = {
            "norm1": init_rmsnorm(d, dtype),
            "attn": init_attention(ks[0], cfg, dtype),
            "norm2": init_rmsnorm(d, dtype),
        }
        if kind == BLOCK_MOE:
            p["moe"] = init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = init_ffn(ks[1], cfg, dtype)
        if cross:
            p["norm_cross"] = init_rmsnorm(d, dtype)
            p["cross"] = init_attention(ks[2], cfg, dtype, cross=True)
        return p
    if kind == BLOCK_MAMBA2:
        return {"norm1": init_rmsnorm(d, dtype), "mixer": init_mamba2(ks[0], cfg, dtype)}
    if kind == BLOCK_MLSTM:
        return {"norm1": init_rmsnorm(d, dtype), "mixer": init_mlstm(ks[0], cfg, dtype)}
    if kind == BLOCK_SLSTM:
        return {"norm1": init_rmsnorm(d, dtype), "mixer": init_slstm(ks[0], cfg, dtype)}
    raise ValueError(kind)


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     cross_len: int = 0) -> Dict:
    cd = dtype_of(cfg.compute_dtype)
    if kind in (BLOCK_ATTN, BLOCK_MOE):
        c = {"attn": init_kv_cache(cfg, batch, max_len, cd)}
        if cross_len:
            xs = (batch, cross_len, cfg.n_kv_heads, cfg.d_head)
            c["cross"] = {"k": jnp.zeros(xs, cd), "v": jnp.zeros(xs, cd)}
        return c
    if kind == BLOCK_MAMBA2:
        return {"mixer": init_ssm_cache(cfg, batch)}
    if kind == BLOCK_MLSTM:
        return {"mixer": init_mlstm_cache(cfg, batch)}
    if kind == BLOCK_SLSTM:
        return {"mixer": init_slstm_cache(cfg, batch)}
    raise ValueError(kind)


def _stack_trees(trees: List[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_lm(key, cfg: ModelConfig) -> Dict:
    """Full parameter pytree.  Scanned period params carry a leading
    (n_full,) axis; tail layers and the shared-attn block are unstacked."""
    dtype = dtype_of(cfg.param_dtype)
    layout = stack_layout(cfg)
    k_embed, k_blocks, k_shared, k_enc, k_head = jax.random.split(key, 5)
    cross = cfg.n_encoder_layers > 0
    params: Dict[str, Any] = {"embed": init_embedding(k_embed, cfg.vocab_size, cfg.d_model, dtype)}

    scan_params = {}
    block_keys = jax.random.split(k_blocks, max(layout.n_full, 1) * layout.period + len(layout.tail))
    for j, kind in enumerate(layout.period_kinds):
        per = [init_block(block_keys[i * layout.period + j], cfg, kind, dtype, cross)
               for i in range(layout.n_full)]
        scan_params[f"pos{j}"] = _stack_trees(per)
    params["blocks"] = scan_params
    params["tail"] = [
        init_block(block_keys[layout.n_full * layout.period + t], cfg, kind, dtype, cross)
        for t, kind in enumerate(layout.tail)
    ]
    if layout.shared_attn:
        params["shared_attn"] = {
            "norm1": init_rmsnorm(cfg.d_model, dtype),
            "attn": init_attention(k_shared, cfg, dtype),
            "norm2": init_rmsnorm(cfg.d_model, dtype),
            "ffn": init_ffn(jax.random.fold_in(k_shared, 1), cfg, dtype),
        }
    if cfg.n_encoder_layers:
        enc_keys = jax.random.split(k_enc, cfg.n_encoder_layers)
        params["encoder"] = {
            "blocks": _stack_trees(
                [init_block(ek, cfg, BLOCK_ATTN, dtype) for ek in enc_keys]),
            "final_norm": init_rmsnorm(cfg.d_model, dtype),
        }
    params["final_norm"] = init_rmsnorm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = init_linear(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int, cross_len: int = 0,
               per_slot_index: bool = False) -> Dict:
    layout = stack_layout(cfg)
    idx = jnp.zeros((batch,) if per_slot_index else (), jnp.int32)
    cache: Dict[str, Any] = {"blocks": {}, "tail": [], "index": idx}
    for j, kind in enumerate(layout.period_kinds):
        per = [init_block_cache(cfg, kind, batch, max_len, cross_len)
               for _ in range(layout.n_full)]
        cache["blocks"][f"pos{j}"] = _stack_trees(per)
    cache["tail"] = [init_block_cache(cfg, kind, batch, max_len, cross_len)
                     for kind in layout.tail]
    if layout.shared_attn:
        shared = [init_block_cache(cfg, BLOCK_ATTN, batch, max_len)
                  for _ in range(layout.n_full)]
        cache["shared"] = _stack_trees(shared)
        n_tail_shared = sum(1 for t in range(len(layout.tail))
                            if (layout.n_full * layout.period + t) % cfg.shared_attn_every == 0)
        cache["tail_shared"] = [init_block_cache(cfg, BLOCK_ATTN, batch, max_len)
                                for _ in range(n_tail_shared)]
    return cache


#: Batch axis of each per-slot cache group (scanned groups lead with the
#: stacked layer axis).
_SLOT_AXIS = {"blocks": 1, "tail": 0, "shared": 1, "tail_shared": 0}


def _slot_index(axis: int, slot):
    return (slice(None),) * axis + (slot,)


def read_slot(cache: Dict, slot) -> Dict:
    """One batch slot's state across the whole per-slot cache
    (``init_cache(..., per_slot_index=True)``): its write offset and every
    KV / recurrent leaf."""
    out = {"index": cache["index"][slot]}
    for key, axis in _SLOT_AXIS.items():
        if key in cache:
            out[key] = jax.tree.map(lambda x: x[_slot_index(axis, slot)],
                                    cache[key])
    return out


def write_slot(cache: Dict, slot, state: Dict) -> Dict:
    """Overwrite one batch slot with a `read_slot` payload.  Jitted with
    the cache donated, this is an in-place per-slot update."""
    out = dict(cache)
    out["index"] = cache["index"].at[slot].set(state["index"])
    for key, axis in _SLOT_AXIS.items():
        if key in cache:
            out[key] = jax.tree.map(
                lambda x, v: x.at[_slot_index(axis, slot)].set(v),
                cache[key], state[key])
    return out


def reset_slot(cache: Dict, slot) -> Dict:
    """Zero one batch slot across the whole cache (continuous batching:
    recurrent SSM/xLSTM states carry no positional mask, so a freed slot
    must be wiped before admitting a new request)."""
    return write_slot(cache, slot,
                      jax.tree.map(jnp.zeros_like, read_slot(cache, slot)))


# --------------------------------------------------------------- forward --
def _whole_stack(path) -> bool:
    """A scanned self-attention K/V leaf: it stays whole in the layer scan."""
    keys = [getattr(p, "key", None) for p in path]
    return len(keys) >= 2 and keys[-2] == "attn" and keys[-1] in ("k", "v")


def _layer_view(stacked, layer):
    """What a period's blocks get of the stacked caches: self-attention K/V
    whole, every other leaf sliced at ``layer``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, c: c if _whole_stack(path) else c[layer], stacked)


def _layer_store(stacked, new_layer, layer):
    """The stacked caches after a period: self-attention K/V as the blocks
    returned them (rows already written), every other leaf's layer written
    back at ``layer``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, c, n: n if _whole_stack(path) else
        jax.lax.dynamic_update_index_in_dim(c, n.astype(c.dtype), layer, 0),
        stacked, new_layer)


def _bar(x, cfg):
    return bf16_cotangent_barrier(x) if cfg.bf16_cotangent else x


def _psum_bar(x, cfg):
    """Keep the TP all-reduce of a row-parallel projection in bf16: without
    this, XLA hoists the next norm's f32 convert above the psum and ships
    2× the bytes (measured on the 110B cell)."""
    if cfg.psum_barrier:
        return jax.lax.optimization_barrier(x)
    return x


def _attn_block(bp, x, cfg, positions, cache, index, encoder_out, kind,
                rope_cache=None, layer=None, kv_len=None):
    aux = jnp.zeros((), jnp.float32)
    h = _bar(rms_norm(x, bp["norm1"]["scale"], cfg.norm_eps), cfg)
    a, attn_cache = attention(
        bp["attn"], h, cfg, positions, causal=True,
        cache=None if cache is None else cache["attn"],
        cache_index=None if cache is None else index,
        cache_layer=layer, rope_cache=rope_cache, kv_len=kv_len,
    )
    x = x + _psum_bar(a, cfg)
    new_cache = None if cache is None else dict(cache, attn=attn_cache)
    if "cross" in bp:
        cd = dtype_of(cfg.compute_dtype)
        hc = _bar(rms_norm(x, bp["norm_cross"]["scale"], cfg.norm_eps), cfg)
        if encoder_out is not None:
            # Train / prefill: project the encoder memory; cache it for decode.
            ck = apply_linear(bp["cross"]["wk"], encoder_out, cd)
            cv = apply_linear(bp["cross"]["wv"], encoder_out, cd)
            ck = ck.reshape(*ck.shape[:-1], cfg.n_kv_heads, cfg.d_head)
            cv = cv.reshape(*cv.shape[:-1], cfg.n_kv_heads, cfg.d_head)
            if new_cache is not None:
                new_cache["cross"] = {"k": ck, "v": cv}
        else:
            if cache is None or "cross" not in cache:
                raise ValueError("decode without encoder_out needs a cross cache")
            ck, cv = cache["cross"]["k"], cache["cross"]["v"]
        q = apply_linear(bp["cross"]["wq"], hc, cd)
        q = q.reshape(*q.shape[:-1], cfg.n_heads, cfg.d_head)
        o = _self_attention_math(q, ck, cv, causal=False)
        c = apply_linear(bp["cross"]["wo"], o.reshape(*hc.shape[:-1], -1), cd)
        x = x + c
    h2 = _bar(rms_norm(x, bp["norm2"]["scale"], cfg.norm_eps), cfg)
    if kind == BLOCK_MOE:
        f, moe_aux, _ = moe_ffn(bp["moe"], h2, cfg)
        aux = aux + moe_aux
    else:
        f = ffn(bp["ffn"], h2, cfg)
    return x + _psum_bar(f, cfg), new_cache, aux


def apply_block(kind, bp, x, cfg, *, positions, cache, index, encoder_out=None,
                rope_cache=None, layer=None, kv_len=None):
    """``layer`` given, ``cache``'s self-attention K/V are the scan's whole
    stacks, written and read at that layer (`_layer_view`).  ``kv_len``,
    the positions attention reads, defaults to ``index`` + the new rows."""
    if kind in (BLOCK_ATTN, BLOCK_MOE):
        return _attn_block(bp, x, cfg, positions, cache, index, encoder_out, kind,
                           rope_cache, layer, kv_len)
    h = _bar(rms_norm(x, bp["norm1"]["scale"], cfg.norm_eps), cfg)
    mixer_cache = None if cache is None else cache["mixer"]
    if kind == BLOCK_MAMBA2:
        m, mc = mamba2_block(bp["mixer"], h, cfg, mixer_cache)
    elif kind == BLOCK_MLSTM:
        m, mc = mlstm_block(bp["mixer"], h, cfg, mixer_cache)
    elif kind == BLOCK_SLSTM:
        m, mc = slstm_block(bp["mixer"], h, cfg, mixer_cache)
    else:
        raise ValueError(kind)
    new_cache = None if cache is None else {"mixer": mc}
    return x + _psum_bar(m, cfg), new_cache, jnp.zeros((), jnp.float32)


def _apply_shared(shared, x, cfg, positions, cache, index, rope_cache=None,
                  layer=None, kv_len=None):
    """Zamba2's weight-shared attention block (own per-depth KV cache)."""
    h = _bar(rms_norm(x, shared["norm1"]["scale"], cfg.norm_eps), cfg)
    a, attn_cache = attention(
        shared["attn"], h, cfg, positions, causal=True,
        cache=None if cache is None else cache["attn"],
        cache_index=None if cache is None else index,
        cache_layer=layer, rope_cache=rope_cache, kv_len=kv_len,
    )
    x = x + a
    h2 = _bar(rms_norm(x, shared["norm2"]["scale"], cfg.norm_eps), cfg)
    x = x + ffn(shared["ffn"], h2, cfg)
    return x, None if cache is None else dict(cache, attn=attn_cache)


def forward(
    params: Dict,
    tokens: Optional[jnp.ndarray],       # (B, S) int32; None if embeds given
    cfg: ModelConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[Dict] = None,
    encoder_out: Optional[jnp.ndarray] = None,
    vision_embeds: Optional[jnp.ndarray] = None,  # (B, P, d) prefix stub
    input_embeds: Optional[jnp.ndarray] = None,   # bypass embedding (encoder stubs)
    decoding: bool = False,
    live: Optional[jnp.ndarray] = None,  # (B,) bool, with a per-slot cache
) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (hidden (B,S,d) — NOT logits; see `logits`/`lm_loss` —,
    new_cache, aux_loss).  ``live`` given, a slot where it is False holds
    no sequence: attention reads none of its cache and its write offset
    stays."""
    cd = dtype_of(cfg.compute_dtype)
    layout = stack_layout(cfg)
    if input_embeds is not None:
        x = input_embeds.astype(cd)
    else:
        x = embed(params["embed"], tokens, cd)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(cd), x], axis=1)
    x = constrain(x, ("dp", None, None))
    B, S, _ = x.shape
    if positions is None:
        offset = cache["index"] if cache is not None else 0
        positions = positions_for(cfg, B, S, offset)
    index = cache["index"] if cache is not None else None
    kv_len = None if live is None else jnp.where(live, index + S, 0)
    rope_cache = rope_tables(cfg, positions) if cfg.hoist_rope else None

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Optional[Dict] = {"blocks": {}, "tail": []} if cache is not None else None

    # ------------------------------------------------------ scanned periods
    if layout.n_full:
        # The stacked per-layer caches ride in the scan carry, so a donated
        # cache is updated in place without a second whole-cache buffer (as
        # scan outputs it would be).  Self-attention K/V stay whole: each
        # layer writes its new rows into the stack and reads its layer where
        # it lies.  Recurrent and cross-attention leaves, a layer's few KB
        # to MB, are sliced out and their layer written back.
        stacked = None
        if cache is not None:
            stacked = {"blocks": cache["blocks"]}
            if layout.shared_attn:
                stacked["shared"] = cache["shared"]

        def period_fn(carry, xs):
            x, aux, stacked = carry
            x = constrain(x, ("dp", None, None))
            if cfg.bf16_cotangent:
                x = bf16_cotangent_barrier(x)
            block_slice, layer = xs
            with jax.named_scope(KV_SCOPE):
                layer_cache = (None if stacked is None else
                               _layer_view(stacked, layer))
            at = None if stacked is None else layer
            if layout.shared_attn:
                x, sc = _apply_shared(
                    params["shared_attn"], x, cfg, positions,
                    None if layer_cache is None else layer_cache["shared"],
                    index, rope_cache, at, kv_len)
            new_layer = {"blocks": {}}
            for j, kind in enumerate(layout.period_kinds):
                cj = (None if layer_cache is None
                      else layer_cache["blocks"][f"pos{j}"])
                x, cj_new, a = apply_block(
                    kind, block_slice[f"pos{j}"], x, cfg,
                    positions=positions, cache=cj, index=index,
                    encoder_out=encoder_out, rope_cache=rope_cache, layer=at,
                    kv_len=kv_len)
                new_layer["blocks"][f"pos{j}"] = cj_new
                aux = aux + a
            if stacked is not None:
                if layout.shared_attn:
                    new_layer["shared"] = sc
                with jax.named_scope(KV_SCOPE):
                    stacked = _layer_store(stacked, new_layer, layer)
            return (x, aux, stacked), None

        body = period_fn
        if cfg.remat == "block":
            body = jax.checkpoint(period_fn, prevent_cse=False)
        elif cfg.remat == "dots":
            # Save matmul outputs: backward recomputes only elementwise ops —
            # in particular the TP psums of wo/w_down outputs are NOT re-run
            # (§Perf: trades ~(B,S,d)·layers HBM for collective wire).
            body = jax.checkpoint(
                period_fn, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_saveable)
        (x, aux_total, stacked), _ = jax.lax.scan(
            body, (x, aux_total, stacked),
            (params["blocks"], jnp.arange(layout.n_full)))
        if cache is not None:
            new_cache.update(stacked)

    # --------------------------------------------------------- tail layers
    shared_i = 0
    for t, kind in enumerate(layout.tail):
        layer_idx = layout.n_full * layout.period + t
        if layout.shared_attn and layer_idx % cfg.shared_attn_every == 0:
            sc = cache["tail_shared"][shared_i] if cache is not None else None
            x, sc_new = _apply_shared(params["shared_attn"], x, cfg, positions,
                                      sc, index, kv_len=kv_len)
            if cache is not None:
                new_cache.setdefault("tail_shared", []).append(sc_new)
            shared_i += 1
        cj = cache["tail"][t] if cache is not None else None
        x, cj_new, a = apply_block(kind, params["tail"][t], x, cfg,
                                   positions=positions, cache=cj, index=index,
                                   encoder_out=encoder_out, rope_cache=rope_cache,
                                   kv_len=kv_len)
        x = constrain(x, ("dp", None, None))
        aux_total = aux_total + a
        if cache is not None:
            new_cache["tail"].append(cj_new)

    if cfg.bf16_cotangent:
        x = bf16_cotangent_barrier(x)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cache is not None:
        new_cache["index"] = (index + S if live is None
                              else jnp.where(live, index + S, index))
    return x, new_cache, aux_total


def logits_fn(params: Dict, hidden: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return unembed(params["embed"], hidden, dtype_of(cfg.logit_dtype))
    return apply_linear(params["unembed"], hidden, dtype_of(cfg.logit_dtype))


# --------------------------------------------------------------- encoder --
def encode(params: Dict, input_embeds: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Bidirectional encoder over stub frontend embeddings (B, S_enc, d)."""
    cd = dtype_of(cfg.compute_dtype)
    x = input_embeds.astype(cd)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, block):
        x = constrain(x, ("dp", None, None))
        h = rms_norm(x, block["norm1"]["scale"], cfg.norm_eps)
        a, _ = attention(block["attn"], h, cfg, positions, causal=False)
        x = x + a
        h2 = rms_norm(x, block["norm2"]["scale"], cfg.norm_eps)
        return x + ffn(block["ffn"], h2, cfg), 0

    fn = body
    if cfg.remat == "block":
        fn = jax.checkpoint(body, prevent_cse=False)
    elif cfg.remat == "dots":
        fn = jax.checkpoint(body, prevent_cse=False,
                            policy=jax.checkpoint_policies.dots_saveable)
    x, _ = jax.lax.scan(fn, x, params["encoder"]["blocks"])
    return rms_norm(x, params["encoder"]["final_norm"]["scale"], cfg.norm_eps)


# ------------------------------------------------------------------ loss --
def lm_loss(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    loss_chunk: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token CE.  batch: inputs/targets (B,S) [+ encoder_embeds /
    vision_embeds / positions].  ``loss_chunk`` bounds the logits
    materialization to (B, chunk, V) — essential for 150k–256k vocabs."""
    encoder_out = None
    if cfg.n_encoder_layers:
        encoder_out = encode(params, batch["encoder_embeds"], cfg)
    hidden, _, aux = forward(
        params, batch["inputs"], cfg,
        positions=batch.get("positions"),
        encoder_out=encoder_out,
        vision_embeds=batch.get("vision_embeds"),
    )
    targets = batch["targets"]
    if hidden.shape[1] != targets.shape[1]:
        # VLM: loss only over the text suffix.
        hidden = hidden[:, hidden.shape[1] - targets.shape[1]:]

    def ce(h_chunk, t_chunk):
        lg = constrain(logits_fn(params, h_chunk, cfg), ("dp", None, "tp"))
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, t_chunk[..., None], axis=-1)[..., 0]
        return (lse - gold).sum()

    B, S, _ = hidden.shape
    if loss_chunk and S % loss_chunk == 0 and S > loss_chunk:
        nc = S // loss_chunk
        hs = hidden.reshape(B, nc, loss_chunk, -1).swapaxes(0, 1)
        ts = targets.reshape(B, nc, loss_chunk).swapaxes(0, 1)
        def body(tot, xt):
            h, t = xt
            return tot + ce(h, t), 0
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (hs, ts))
    else:
        total = ce(hidden, targets)
    n_tok = jnp.array(B * S, jnp.float32)
    loss = total / n_tok + aux
    return loss, {"loss": loss, "ce": total / n_tok, "aux": aux}
