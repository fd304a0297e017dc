"""On-chip numerics of the compiled (Mosaic) kernels — chip_smoke phase (a).

The CPU suite runs the kernel bodies in interpret mode and
tests/test_tpu_compile.py proves Mosaic accepts them; what only the chip
can show is that the COMPILED kernels compute the right numbers. Each
check runs a kernel as compiled for the attached TPU against the XLA
reference it replaces, at the serving widths of the smoke model
(Qwen3-1.7B: 16 query / 8 kv heads of 128, pages of 16):

  1. cache appends: one row (bf16), int8 + scale planes, T-token verify
  2. decode attention: paged kernel, merged out-of-cache token, int8
     pages with per-page scales, each reading the LAST layer of the
     whole stacked cache (the kernel's operand since PR 29); and the
     kernel at the benchmark cell's shape (olmo2-1b.chat: 32 slots, MHA
     16 x 128, a 256-page table over 2,560 pages, layer 11 of the
     16-layer 5 GiB pool) with lengths up to the full 4,096 and stats
     out, so the accumulation across superblocks is checked where it is
     long, and against the same layer as a one-layer cache bit for bit
  3. chunked-prefill attention over the paged cache
  4. the mixed step's in-place row write and the ragged mixed (decode +
     prefill) kernel, bf16 and int8 + scales, at the LAST layer of the
     whole stacked cache (its one operand form since PR 41)
  5. model level, 2 layers at full width: merged decode, Pallas prefill
     and the fused mixed step against their XLA twins, for the smoke
     model and for gpt-oss's attention (head_dim 64, sinks, windows)
  6. the other families' kernels: head_dim 64 with sinks + window
     (gpt-oss, in the 128-lane rows the model stores it in), MLA latent
     decode, the int8 grouped matmul (MoE)

Inputs and the XLA references are made on the host's CPU backend (a
plain f32 reference, and no TPU compile per eager op); only the kernels'
own operands are put on the chip, so what runs there is the kernels.

On a host of several chips (``chiprun --chips 4``) it runs ONE check
and nothing else: the fused mixed step under a ``tp`` mesh over all of
them against the same step on one chip (the in-place row write and both
kernels under shard_map: what exists only across chips).

Needs the TPU: exits 3 before any check when JAX finds none. Exits 1 on
any mismatch — nothing here is informational, and a kernel the chip's
compiler refuses raises.

Run: python scripts/validate_tpu_kernels.py
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models import llama
from dynamo_tpu.models import mla as _mla
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops.kv_cache_update_pallas import (
    kv_cache_append,
    kv_cache_append_quantized,
    kv_cache_append_tokens,
    kv_cache_append_tokens_xla,
)
from dynamo_tpu.ops.mla_attention_pallas import (
    mla_decode_attention_merged,
    mla_paged_decode_attention,
)
from dynamo_tpu.ops.moe_gmm_pallas import moe_grouped_matmul, ragged_int8_xla
from dynamo_tpu.ops.paged_attention_pallas import (
    paged_decode_attention,
    paged_prefill_attention,
)
from dynamo_tpu.ops.ragged_paged_attention_pallas import ragged_mixed_attention
from dynamo_tpu.utils.compile_cache import configure_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_CONFIG = os.path.join(REPO, "configs", "qwen3-1.7b", "config.json")

# smoke-model widths; B decode rows over M-page tables, L stacked layers
B, H, HKV, D, L, BS, M = 8, 16, 8, 128, 2, 16, 32
N = (B + 1) * M + 1  # page 0 is the sacrificial page, never a real one
T, HIST, VALID = 256, 37, 200  # prefill chunk rows, cached history, real rows
SCALE = D**-0.5


def chip(*xs):
    """The operands on the accelerator: a jitted kernel runs where its
    committed arguments live, whatever the default device is."""
    # through numpy: always a fresh buffer (some operands are donated)
    out = tuple(
        jax.tree.map(lambda a: jax.device_put(np.asarray(a), jax.devices()[0]),
                     x)
        for x in xs
    )
    return out if len(out) > 1 else out[0]


def on_its_own(append):
    """An append kernel as a program of its own. Its aliased outputs are
    pinned to HBM (``kv_cache_update_pallas._in_hbm``); as the ROOT of a
    program that donates the caches the pinned result meets an unpinned
    parameter and the compiler's alias check refuses the pair. Every step
    program has something behind the call; here a barrier stands in."""
    return jax.jit(lambda *args: lax.optimization_barrier(append(*args)),
                   donate_argnums=(2, 3))


class Checker:
    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name, got, ref, rtol=2e-2, atol=2e-2):
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        err = float(np.max(np.abs(got - ref))) if got.size else 0.0
        good = (
            got.shape == ref.shape
            and bool(np.isfinite(got).all())
            and np.allclose(got, ref, rtol=rtol, atol=atol)
        )
        print(f"{'PASS' if good else 'FAIL'} {name}  max|err|={err:.2e}",
              flush=True)
        if not good:
            self.failed.append(name)


def _quantize_pages(x):
    """bf16 pages -> (int8 pages, per-page f32 scales [N])."""
    xf = np.asarray(x, np.float32)  # [Hkv, N, bs, D]
    s = np.maximum(np.abs(xf).max(axis=(0, 2, 3)) / 127.0, 1e-12)  # [N]
    q = np.clip(np.round(xf / s[None, :, None, None]), -127, 127)
    return jnp.asarray(q, jnp.int8), jnp.asarray(s, jnp.float32)


def check_kernels(check: Checker) -> None:
    rng = np.random.default_rng(0)
    ks = jax.random.split(jax.random.key(0), 12)
    kc = jax.random.normal(ks[0], (L, HKV, N, BS, D), jnp.bfloat16)
    vc = jax.random.normal(ks[1], (L, HKV, N, BS, D), jnp.bfloat16)
    k_new = jax.random.normal(ks[2], (L, B, HKV, D), jnp.bfloat16)
    v_new = jax.random.normal(ks[3], (L, B, HKV, D), jnp.bfloat16)
    q = jax.random.normal(ks[4], (B, H, D), jnp.bfloat16)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    tables = jnp.asarray(pages[: B * M].reshape(B, M))
    p_table = jnp.asarray(pages[B * M : (B + 1) * M])
    seq_lens = jnp.asarray(
        [1, BS - 1, BS, BS + 1, 3 * BS + 5, M * BS // 2, M * BS - 1, M * BS],
        jnp.int32,
    )
    hist = seq_lens - 1
    blk, off = att.decode_slot_indices(tables, hist, BS)

    # ---- 1. cache appends ----
    ref_k, ref_v = kc, vc
    for l in range(L):
        ref_k = ref_k.at[l, :, blk, off].set(k_new[l])
        ref_v = ref_v.at[l, :, blk, off].set(v_new[l])
    got_k, got_v = on_its_own(kv_cache_append)(
        *chip(k_new, v_new, kc, vc, blk, off))
    check("kv_cache_append k", got_k, ref_k, rtol=0, atol=0)
    check("kv_cache_append v", got_v, ref_v, rtol=0, atol=0)

    kq = jnp.stack([_quantize_pages(kc[l])[0] for l in range(L)])
    vq = jnp.stack([_quantize_pages(vc[l])[0] for l in range(L)])
    # small resident scales so the new rows grow some pages (requant)
    k_pl = jnp.full((L, N), 0.01, jnp.float32).at[:, blk[1]].set(1.0)
    v_pl = jnp.full((L, N), 0.02, jnp.float32).at[:, blk[2]].set(1.0)
    ref = [
        [att.write_decode_token_to_cache_quantized(
            c[l], pl[l], new[l], tables, hist)
         for l in range(L)]
        for c, pl, new in ((kq, k_pl, k_new), (vq, v_pl, v_new))
    ]
    got_k, got_v, got_ks, got_vs, n_req = kv_cache_append_quantized(
        *chip(k_new, v_new, kq, vq, k_pl, v_pl, blk, off)
    )
    for name, got, side in (("k", (got_k, got_ks), ref[0]),
                            ("v", (got_v, got_vs), ref[1])):
        check(f"kv_cache_append_quantized {name} pages", got[0],
              jnp.stack([r[0] for r in side]), rtol=0, atol=0)
        check(f"kv_cache_append_quantized {name} scales", got[1],
              jnp.stack([r[1] for r in side]), rtol=1e-6, atol=0)
    check("kv_cache_append_quantized requants counted", int(n_req) > 0, True)

    Tv = 5  # speculative verify window; starts cover no-cross and cross
    start = jnp.asarray([0, 5, BS - 2, 2 * BS - 1, BS, 7, BS - 4, 3],
                        jnp.int32)
    pos = start[:, None] + jnp.arange(Tv)[None, :]
    blk_t = jnp.take_along_axis(tables, pos // BS, axis=1)
    off_t = pos % BS
    kt = jax.random.normal(ks[5], (L, B, Tv, HKV, D), jnp.bfloat16)
    vt = jax.random.normal(ks[6], (L, B, Tv, HKV, D), jnp.bfloat16)
    ref_k, ref_v = kv_cache_append_tokens_xla(kt, vt, kc, vc, blk_t, off_t)
    got_k, got_v = on_its_own(kv_cache_append_tokens)(
        *chip(kt, vt, kc, vc, blk_t, off_t)
    )
    check("kv_cache_append_tokens k", got_k[:, :, 1:], ref_k[:, :, 1:],
          rtol=0, atol=0)
    check("kv_cache_append_tokens v", got_v[:, :, 1:], ref_v[:, :, 1:],
          rtol=0, atol=0)

    # ---- 2. decode attention: the whole L-layer cache as the operand,
    # read at its LAST layer (a kernel that ignored the index would read
    # layer 0's pages) ----
    l = L - 1
    ref = att.decode_attention_xla(q, kc[l], vc[l], tables, seq_lens, SCALE)
    got = paged_decode_attention(
        *chip(q, kc, vc), l, *chip(tables, seq_lens), SCALE
    )
    check(f"paged_decode_attention layer {l} of {L}", got, ref)

    kc1 = kc.at[l, :, blk, off].set(k_new[l])
    vc1 = vc.at[l, :, blk, off].set(v_new[l])
    ref = att.decode_attention_xla(q, kc1[l], vc1[l], tables, hist + 1, SCALE)
    got = att.decode_attention_merged(
        *chip(q, k_new[l], v_new[l], kc, vc), l, *chip(tables, hist), SCALE
    )
    check(f"decode_attention_merged layer {l} of {L}", got, ref)

    (kq_l, ksc), (vq_l, vsc) = _quantize_pages(kc[l]), _quantize_pages(vc[l])
    ref = att.decode_attention_xla(
        q, kq_l, vq_l, tables, seq_lens, SCALE, k_scales=ksc, v_scales=vsc
    )
    ksc_c, vsc_c = chip(ksc, vsc)
    got = paged_decode_attention(
        *chip(q, kq, vq), l, *chip(tables, seq_lens), SCALE,
        k_scales=ksc_c, v_scales=vsc_c,
    )
    check(f"paged_decode_attention int8+scales layer {l} of {L}", got, ref)

    # ---- 3./4. prefill + ragged mixed attention (write-before-attend) ----
    q_chunk = jax.random.normal(ks[7], (T, H, D), jnp.bfloat16)
    k_chunk = jax.random.normal(ks[8], (T, HKV, D), jnp.bfloat16)
    v_chunk = jax.random.normal(ks[9], (T, HKV, D), jnp.bfloat16)
    kcw = att.write_chunk_to_cache(kc[l], k_chunk, p_table, jnp.int32(HIST))
    vcw = att.write_chunk_to_cache(vc[l], v_chunk, p_table, jnp.int32(HIST))
    ref_chunk = att.chunk_attention_with_cache_xla(
        q_chunk, k_chunk, v_chunk, kcw, vcw, p_table, jnp.int32(HIST),
        jnp.int32(VALID), SCALE,
    )
    got = paged_prefill_attention(
        *chip(q_chunk, kcw, vcw, p_table, jnp.int32(HIST)), SCALE
    )
    check("paged_prefill_attention", got[:VALID], ref_chunk[:VALID])

    ref_dec = att.decode_attention_xla(q, kcw, vcw, tables, seq_lens, SCALE)
    segment = (p_table[None], jnp.asarray([HIST], jnp.int32),
               jnp.asarray([VALID], jnp.int32))
    # the mixed step's write: the chunk's rows land in place in the
    # whole cache at its LAST layer, where the slab write put them (page
    # 0 apart: no row here is padded, so it stays untouched too)
    p_blk, p_off = att.chunk_slot_indices(
        p_table[None], jnp.asarray([HIST], jnp.int32), T, BS)
    kc_w, vc_w = kc.at[l].set(kcw), vc.at[l].set(vcw)
    kc_c, vc_c = chip(kc, vc)
    kc_c = jax.jit(att.write_rows_to_cache, donate_argnums=0)(
        kc_c, l, *chip(k_chunk, p_blk, p_off))
    vc_c = jax.jit(att.write_rows_to_cache, donate_argnums=0)(
        vc_c, l, *chip(v_chunk, p_blk, p_off))
    check(f"write_rows_to_cache k layer {l} of {L}", kc_c, kc_w,
          rtol=0, atol=0)
    check(f"write_rows_to_cache v layer {l} of {L}", vc_c, vc_w,
          rtol=0, atol=0)
    # both of the mixed step's kernels read that layer of the whole
    # cache (a kernel that ignored the index would read layer 0's pages)
    o_dec, o_chunks = ragged_mixed_attention(
        *chip(q, q_chunk[None]), kc_c, vc_c, l,
        *chip(tables, seq_lens, *segment), SCALE
    )
    check(f"ragged_mixed_attention decode rows layer {l} of {L}", o_dec,
          ref_dec)
    check(f"ragged_mixed_attention chunk rows layer {l} of {L}",
          o_chunks[0, :VALID], ref_chunk[:VALID])

    kqw, ksw = _quantize_pages(kcw)
    vqw, vsw = _quantize_pages(vcw)
    # the references read the SAME int8 pages through the same scales
    kdq = (kqw.astype(jnp.float32) * ksw[None, :, None, None]).astype(
        jnp.bfloat16)
    vdq = (vqw.astype(jnp.float32) * vsw[None, :, None, None]).astype(
        jnp.bfloat16)
    ref_dec = att.decode_attention_xla(q, kdq, vdq, tables, seq_lens, SCALE)
    pos_c = HIST + np.arange(T)
    rows = (np.asarray(p_table)[pos_c // BS], pos_c % BS)
    ref_chunk = att.chunk_attention_with_cache_xla(
        q_chunk, kdq[:, rows[0], rows[1]].swapaxes(0, 1),
        vdq[:, rows[0], rows[1]].swapaxes(0, 1), kdq, vdq, p_table,
        jnp.int32(HIST), jnp.int32(VALID), SCALE,
    )
    ksw_c, vsw_c = chip(ksw, vsw)
    o_dec, o_chunks = ragged_mixed_attention(
        *chip(q, q_chunk[None], kq.at[l].set(kqw), vq.at[l].set(vqw)), l,
        *chip(tables, seq_lens, *segment), SCALE,
        k_scales=ksw_c, v_scales=vsw_c,
    )
    check(f"ragged_mixed_attention int8+scales decode rows layer {l} of {L}",
          o_dec, ref_dec)
    check(f"ragged_mixed_attention int8+scales chunk rows layer {l} of {L}",
          o_chunks[0, :VALID], ref_chunk[:VALID])
    got = paged_prefill_attention(
        *chip(q_chunk, kqw, vqw, p_table, jnp.int32(HIST)), SCALE,
        k_scales=ksw_c, v_scales=vsw_c,
    )
    check("paged_prefill_attention int8+scales", got[:VALID],
          ref_chunk[:VALID])


def check_cell_decode(check: Checker) -> None:
    """``paged_decode_attention`` as ``olmo2-1b.chat`` calls it (the
    merged path: stats out) at real lengths: the benchmark's reference
    check reads two positions of 48-token prompts, which never leave the
    first superblock of 128 tokens."""
    Bc, Hc, Mc, Nc, Lc, lc = 32, 16, 256, 2560, 16, 11
    rng = np.random.default_rng(27)
    ks = jax.random.split(jax.random.key(27), 3)
    q = jax.random.normal(ks[0], (Bc, Hc, D), jnp.bfloat16)
    # the cell's whole pool, 2 x 2.5 GiB, made on the chip (too much to
    # draw on the host and send) and read at layer 11 where it lies; the
    # host's reference gets a copy of that layer
    with jax.default_device(jax.devices()[0]):
        kc = jax.random.normal(ks[1], (Lc, Hc, Nc, BS, D), jnp.bfloat16)
        vc = jax.random.normal(ks[2], (Lc, Hc, Nc, BS, D), jnp.bfloat16)
    kc_l, vc_l = (jnp.asarray(np.asarray(c[lc])) for c in (kc, vc))
    # a row's pages are distinct; rows share the pool, as a prefix does
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, Nc))[:Mc] for _ in range(Bc)
    ]).astype(np.int32))
    edges = [0, 1, BS, BS + 1, 8 * BS - 1, 8 * BS, 8 * BS + 1, 4000,
             Mc * BS - 1, Mc * BS]
    lens = np.concatenate([
        edges, rng.integers(2, 4000, Bc - len(edges) - 2), [0, 0]
    ]).astype(np.int32)
    # dead rows first, last and between the live ones: the kernel walks
    # a row's own pages, none for these
    lens[[13, 14, 21]] = 0
    live = lens > 0
    seq_lens = jnp.asarray(lens)
    ro, rm, rl = att._history_attention_xla(
        q[:, None], kc_l, vc_l, tables, seq_lens, SCALE
    )  # [B, Hkv, 1, G(, D)]
    rows = chip(tables, seq_lens)
    o, m, l = paged_decode_attention(
        chip(q), kc, vc, lc, *rows, SCALE, return_stats=True
    )
    # the same layer handed over as a one-layer cache: the same kernel on
    # the same bytes, so bit for bit
    slab = paged_decode_attention(
        chip(q), *chip(kc_l[None], vc_l[None]), 0, *rows, SCALE,
        return_stats=True,
    )
    name = (f"paged_decode_attention cell shape, layer {lc} of {Lc}, "
            f"lengths {lens.min()}-{lens.max()}")
    for part, whole, one in zip(("out", "m", "l"), (o, m, l), slab):
        check(f"{name}: {part} == the slab's as [None], layer 0", whole,
              one, rtol=0, atol=0)
    check(f"{name}: all rows finite", np.isfinite(np.asarray(
        o, np.float32)).all(), True)
    # what decode_attention_merged folds for a row with no history
    for part, got, want in (("out", o, 0.0), ("m", m, -1e30), ("l", l, 0.0)):
        dead = np.asarray(got, np.float32)[~live]
        check(f"{name}: {part} of the {(~live).sum()} dead rows", dead,
              np.full_like(dead, want), rtol=0, atol=0)
    check(f"{name}: out", np.asarray(o, np.float32)[live],
          np.asarray(ro, np.float32).reshape(Bc, Hc, D)[live])
    check(f"{name}: m", np.asarray(m)[live], np.asarray(rm)[live, :, 0])
    # l sums up to 4,096 terms: relative only
    check(f"{name}: l", np.asarray(l)[live] / np.asarray(rl)[live, :, 0],
          np.ones_like(np.asarray(l)[live]))


def smoke_model() -> ModelConfig:
    """The smoke model cut to 2 layers, every width as published."""
    with open(SMOKE_CONFIG) as f:
        return dataclasses.replace(
            ModelConfig.from_hf_config(json.load(f)), num_layers=2
        )


def check_model(check: Checker, cfg: ModelConfig, name: str) -> None:
    """The Pallas flavor of each serving program of ``cfg`` against its
    XLA twin."""
    # one program for the whole seeded tree, on the chip
    with jax.default_device(jax.devices()[0]):
        params = jax.jit(
            lambda: llama.init_params(cfg, jax.random.key(1))
        )()
    rng = np.random.default_rng(1)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    tables = jnp.asarray(pages[: B * M].reshape(B, M))
    p_table = jnp.asarray(pages[B * M : (B + 1) * M])
    seq_lens = jnp.asarray(
        [1, BS - 1, BS, BS + 1, 3 * BS + 5, M * BS // 2, M * BS - 9,
         M * BS - 8], jnp.int32,  # room for the 4 steps below
    )
    shape_k, shape_v = llama.kv_cache_shapes(cfg, N, BS)
    kc0 = jnp.asarray(rng.standard_normal(shape_k, np.float32), jnp.bfloat16)
    vc0 = jnp.asarray(rng.standard_normal(shape_v, np.float32), jnp.bfloat16)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, B)), jnp.int32)
    p_toks = jnp.asarray(rng.integers(0, cfg.vocab_size, T), jnp.int32)
    zeros_i = jnp.zeros(B, jnp.int32)
    greedy = (zeros_i, zeros_i, jnp.zeros(B, jnp.float32), zeros_i,
              jnp.ones(B, jnp.float32))  # seeds, steps, temps, top_ks, top_ps
    tables, p_table, seq_lens, toks, p_toks, greedy = chip(
        tables, p_table, seq_lens, toks, p_toks, greedy)
    chunk2 = chip(jnp.asarray([HIST + VALID], jnp.int32),
                  jnp.asarray([T - VALID], jnp.int32))

    out = {}
    for tag, up in (("xla", False), ("pallas", True)):
        kcx, vcx = chip(kc0, vc0)  # fresh (donated) copies per path
        logits_all = []
        # fixed (not argmax-chained) tokens: near-flat random-weight
        # logits would let a tie flip send the two paths down different
        # sequences
        for step in range(3):
            pos = seq_lens - 1 + step
            logits, kcx, vcx = llama.decode_step(
                params, cfg, toks[step], pos, tables, pos + 1, kcx, vcx,
                use_pallas=up,
            )
            logits_all.append(np.asarray(logits, np.float32))
        p_logits, kcx, vcx = llama.prefill(
            params, cfg, p_toks, p_table, *chip(jnp.int32(HIST),
                                                jnp.int32(VALID)),
            kcx, vcx, use_pallas=up,
        )
        m_toks, m_logits, kcx, vcx = llama.mixed_step(
            params, cfg, toks[3], seq_lens + 2, tables, seq_lens + 3,
            *greedy, p_toks[None], p_table[None], *chunk2, kcx, vcx,
            use_pallas=up,
        )[:4]
        # the pool the programs leave, apart from what nothing reads:
        # trash page 0 and the slots of the mixed step's padded rows
        # (their K and V hang on attention over padding, which the two
        # paths define differently)
        pad = HIST + T + np.arange(VALID)
        pools = []
        for pool in (kcx, vcx):
            pool = np.array(pool, np.float32)
            pool[:, :, np.asarray(p_table)[pad // BS], pad % BS] = 0.0
            pools.append(pool[:, :, 1:])
        out[tag] = (np.stack(logits_all), np.asarray(p_logits, np.float32),
                    np.asarray(m_logits, np.float32), *pools)
    # logits of a random-weight bf16 model: O(1) values, compared at
    # kernel-grade tolerance (different reduction orders per path)
    tol = dict(rtol=5e-2, atol=2e-1)
    check(f"{name}: decode_step merged == XLA (logits, 3 steps)",
          out["pallas"][0], out["xla"][0], **tol)
    check(f"{name}: prefill Pallas == XLA (last-row logits)",
          out["pallas"][1], out["xla"][1], **tol)
    check(f"{name}: mixed_step Pallas == XLA (segment logits)",
          out["pallas"][2], out["xla"][2], **tol)
    # every real row the steps wrote lies where the XLA twin put it (the
    # mixed step's in-place write; unwritten rows are equal bit for bit)
    check(f"{name}: the K pool after the steps, Pallas == XLA",
          out["pallas"][3], out["xla"][3], **tol)
    check(f"{name}: the V pool after the steps, Pallas == XLA",
          out["pallas"][4], out["xla"][4], **tol)


def check_mesh_mixed(check: Checker, cfg: ModelConfig) -> None:
    """The fused mixed step under a ``tp`` mesh over every chip of the
    host (the in-place row write and both kernels under shard_map on each
    device's kv heads) against the same step on one chip: the segment's
    logits, and the real rows of the pool it leaves."""
    from dynamo_tpu.parallel import mesh as pm

    tp = len(jax.devices())
    mesh = pm.make_mesh(pm.MeshConfig(tp=tp))
    params = jax.jit(lambda: llama.init_params(cfg, jax.random.key(1)))()
    rng = np.random.default_rng(1)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    tables = pages[: B * M].reshape(B, M)
    p_table = pages[B * M : (B + 1) * M]
    seq_lens = np.asarray(
        [1, BS - 1, BS, BS + 1, 3 * BS + 5, M * BS // 2, 0, M * BS - 8],
        np.int32)  # a dead slot among them
    shape_k, shape_v = llama.kv_cache_shapes(cfg, N, BS)
    kc0 = jnp.asarray(rng.standard_normal(shape_k, np.float32), jnp.bfloat16)
    vc0 = jnp.asarray(rng.standard_normal(shape_v, np.float32), jnp.bfloat16)
    zeros_i = np.zeros(B, np.int32)
    step = (
        rng.integers(0, cfg.vocab_size, B).astype(np.int32),
        np.maximum(seq_lens - 1, 0), tables, seq_lens,
        zeros_i, zeros_i, np.zeros(B, np.float32), zeros_i,
        np.ones(B, np.float32),  # greedy
        rng.integers(0, cfg.vocab_size, (1, T)).astype(np.int32),
        p_table[None], np.asarray([HIST], np.int32),
        np.asarray([VALID], np.int32),
    )
    out = {}
    for tag, m in (("one chip", None), (f"tp={tp}", mesh)):
        if m is None:
            p, kcx, vcx = chip(params, kc0, vc0)
        else:
            sharding = pm.cache_sharding(m, cfg)
            p = pm.shard_params(params, m)
            kcx = jax.device_put(np.asarray(kc0), sharding)
            vcx = jax.device_put(np.asarray(vc0), sharding)
        _toks, logits, kcx, vcx = llama.mixed_step(
            p, cfg, *step, kcx, vcx, use_pallas=True, mesh=m)[:4]
        pad = HIST + VALID + np.arange(T - VALID)  # the padded rows' slots
        pools = []
        for pool in (kcx, vcx):
            pool = np.array(pool, np.float32)
            pool[:, :, p_table[pad // BS], pad % BS] = 0.0
            pools.append(pool[:, :, 1:])
        out[tag] = (np.asarray(logits, np.float32), *pools)
    one, many = out["one chip"], out[f"tp={tp}"]
    tol = dict(rtol=5e-2, atol=2e-1)
    check(f"mixed_step tp={tp} == one chip (segment logits)", many[0],
          one[0], **tol)
    check(f"mixed_step tp={tp} == one chip (the K pool it leaves)", many[1],
          one[1], **tol)
    check(f"mixed_step tp={tp} == one chip (the V pool it leaves)", many[2],
          one[2], **tol)


def check_other_families(check: Checker) -> None:
    rng = np.random.default_rng(2)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    tables = jnp.asarray(pages[: B * M].reshape(B, M))
    seq_lens = jnp.asarray(
        [1, BS - 1, BS, BS + 1, 3 * BS + 5, M * BS // 2, M * BS - 1, M * BS],
        jnp.int32,
    )
    # gpt-oss geometry: head_dim 64, sinks, a sliding window whose floor
    # lies past the longer rows' first superblocks. The reference is the
    # head as the checkpoint has it, 64 lanes; the kernel gets it as the
    # model hands it over, in rows of llama.kv_lanes = 128 lanes with the
    # upper half zero (its own DMAs slice whole lane tiles only)
    D64 = 64
    lanes = llama.kv_lanes(ModelConfig.tiny(head_dim=D64))
    ks = jax.random.split(jax.random.key(7), 4)
    q64 = jax.random.normal(ks[0], (B, H, D64), jnp.bfloat16)
    kc64 = jax.random.normal(ks[1], (L, HKV, N, BS, D64), jnp.bfloat16)
    vc64 = jax.random.normal(ks[2], (L, HKV, N, BS, D64), jnp.bfloat16)
    sinks = jax.random.normal(ks[3], (H,), jnp.float32)

    def in_lanes(x):
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, lanes - D64),))

    for name, window, snk in (("plain", 0, None), ("window", 10, None),
                              ("sinks+window", 10, sinks)):
        ref = att.decode_attention_xla(
            q64, kc64[L - 1], vc64[L - 1], tables, seq_lens, D64**-0.5,
            window=window, sinks=snk,
        )
        got = att.decode_attention(
            *chip(in_lanes(q64), in_lanes(kc64), in_lanes(vc64)), L - 1,
            *chip(tables, seq_lens), D64**-0.5, use_pallas=True,
            window=window, sinks=None if snk is None else chip(snk),
        )
        check(f"decode kernel D=64 in {lanes} lanes {name}, layer {L - 1} "
              f"of {L}", got[..., :D64], ref)
        check(f"decode kernel D=64 {name}: the upper lanes stay zero",
              np.asarray(got[..., D64:], np.float32),
              np.zeros((B, H, lanes - D64), np.float32), rtol=0, atol=0)

    # MLA latent kernels at DeepSeek widths: the whole caches and layer 1
    # of 2 by its index, the rope row of 64 in the pool's 128 lanes
    # (llama.rope_lanes); the reference reads layer 1's slab at 64 lanes.
    # Then a ragged batch with dead slots, whose table entries past a
    # row's last page are out of range: the walk reads none of them
    C, R, Hm = 512, 64, 16
    ks = jax.random.split(jax.random.key(5), 6)
    q_eff = jax.random.normal(ks[0], (B, Hm, C), jnp.bfloat16)
    q_pe = jax.random.normal(ks[1], (B, Hm, R), jnp.bfloat16)
    cc = jax.random.normal(ks[2], (2, 1, N, BS, C), jnp.bfloat16)
    pc = jax.random.normal(ks[3], (2, 1, N, BS, R), jnp.bfloat16)
    pc_lanes = jnp.pad(pc, [(0, 0)] * 4 + [(0, 128 - R)])
    mscale = (C + R) ** -0.5
    c_new = jax.random.normal(ks[4], (B, C), jnp.bfloat16)
    pe_new = jax.random.normal(ks[5], (B, R), jnp.bfloat16)
    ragged = jnp.asarray(
        [0, 1, BS, 0, 8 * BS, 8 * BS + 1, 0, M * BS - 1], jnp.int32)
    for name, lens in (("", seq_lens), (" ragged, dead slots", ragged)):
        live = np.asarray(lens) > 0
        held = np.arange(M)[None, :] * BS < np.asarray(lens)[:, None]
        tbl = jnp.where(held, tables, 2**30) if name else tables
        ref = _mla.mla_decode_attention_xla(
            q_eff, q_pe, cc[1], pc[1], tables, lens, mscale
        )
        got = mla_paged_decode_attention(
            *chip(q_eff, q_pe, cc, pc_lanes), 1, *chip(tbl, lens), mscale
        )
        check(f"mla_paged_decode_attention{name}", got[live], ref[live])
        check(f"mla_paged_decode_attention{name}: a dead slot reads 0",
              got[~live], np.zeros_like(ref[~live]), rtol=0, atol=0)
        hist = jnp.maximum(lens - 1, 0)
        blk, off = att.decode_slot_indices(tables, hist, BS)
        ref = _mla.mla_decode_attention_xla(
            q_eff, q_pe, cc[1].at[0, blk, off].set(c_new),
            pc[1].at[0, blk, off].set(pe_new), tables, hist + 1, mscale,
        )
        got = mla_decode_attention_merged(
            *chip(q_eff, q_pe, c_new, pe_new, cc, pc_lanes), 1,
            *chip(tbl, lens - 1), mscale
        )
        # (a slot with no history attends its own token alone, in both)
        check(f"mla_decode_attention_merged{name}", got[live], ref[live])

    # the grouped matmul (MoE experts), the LAST layer of a 2-layer
    # stack by its index: int8 stacks with ragged groups incl. empty
    # ones and a padded tail, then DeepSeek-proportioned slices
    kg = jax.random.split(jax.random.key(11), 3)
    for name, (r, k, n, x, sizes) in (
        ("ragged+pad", (96, 512, 256, 8, [17, 0, 31, 5, 11, 9, 7, 0])),
        ("deepseek-ish", (256, 7168, 2048, 4, [64, 128, 0, 64])),
    ):
        gs = jnp.asarray(np.array(sizes, np.int32))
        lhs = jax.random.normal(kg[0], (r, k), jnp.bfloat16)
        wq = jax.random.randint(kg[1], (2, x, k, n), -127, 128, jnp.int8)
        ws = jax.random.uniform(kg[2], (2, x, n), jnp.float32, 0.5, 2.0)
        ref = ragged_int8_xla(lhs, wq[1], ws[1], gs)
        ref = jnp.where(jnp.arange(r)[:, None] < int(np.sum(sizes)), ref, 0.0)
        lhs_c, wq_c, ws_c, gs_c = chip(lhs, wq, ws, gs)
        got = moe_grouped_matmul(lhs_c, wq_c, gs_c, layer=1, scale=ws_c)
        # K-long bf16 dot products of O(100)-magnitude int8 weights
        check(f"moe_grouped_matmul int8 {name}", got, ref, rtol=2e-2,
              atol=2e-2 * float(np.sqrt(k)) * 127)
    # bf16 stacks at the expert cells' widths: a decode batch (most rows
    # behind every group, NaN in them), LFM2's down projection (K 1792:
    # chunks of 256 rows), and a 512-token chunk's rows over row tiles
    rng = np.random.default_rng(11)
    for name, (r, k, n, x, in_groups) in (
        ("olmoe gate, decode", (256, 2048, 1024, 64, 24)),
        ("lfm2 down, decode", (128, 1792, 2048, 32, 40)),
        ("olmoe gate, mixed step", (4352, 2048, 1024, 64, 4300)),
    ):
        sizes = np.bincount(rng.integers(0, x, in_groups), minlength=x)
        gs = jnp.asarray(sizes, jnp.int32)
        lhs = jax.random.normal(kg[0], (r, k), jnp.bfloat16)
        lhs = lhs.at[in_groups:].set(jnp.nan)
        w = (0.02 * jax.random.normal(kg[1], (2, x, k, n))).astype(
            jnp.bfloat16)
        ref = lax.ragged_dot(lhs, w[1], gs,
                             preferred_element_type=jnp.float32)
        ref = jnp.where(jnp.arange(r)[:, None] < in_groups, ref, 0.0)
        lhs_c, w_c, gs_c = chip(lhs, w, gs)
        got = moe_grouped_matmul(lhs_c, w_c, gs_c, layer=1)
        check(f"moe_grouped_matmul bf16 {name}", got, ref, rtol=2e-2,
              atol=2e-2)


def main() -> int:
    cache_dir = configure_compile_cache()
    dev = jax.devices()[0]
    print("DEVICE " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "compile_cache": cache_dir,
    }), flush=True)
    if dev.platform != "tpu":
        print("validate_tpu_kernels: JAX found no TPU — the compiled "
              "kernels cannot be checked here", file=sys.stderr)
        return 3
    check = Checker()
    if len(jax.devices()) > 1:
        # a host of several chips: only what exists only across chips
        check_mesh_mixed(check, smoke_model())
        if check.failed:
            print(f"FAILURES ({len(check.failed)}): {check.failed}",
                  flush=True)
            return 1
        print("ALL PASS", flush=True)
        return 0
    with jax.default_device(jax.devices("cpu")[0]):
        check_kernels(check)
        check_cell_decode(check)
        smoke = smoke_model()
        check_model(check, smoke, "smoke model")
        # gpt-oss's attention at the smoke model's widths: a head of 64
        # (in rows of llama.kv_lanes = 128 lanes), sinks, a sliding and a
        # full layer
        check_model(check, dataclasses.replace(
            smoke, head_dim=64, attn_sinks=True, layer_windows=(128, 0),
            qk_norm=False,
        ), "head 64 + sinks + windows")
        check_other_families(check)
    if check.failed:
        print(f"FAILURES ({len(check.failed)}): {check.failed}", flush=True)
        return 1
    print("ALL PASS", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
