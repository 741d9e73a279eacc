"""Compiled-artifact analysis: collective-byte extraction from (SPMD) HLO
text + the three-term roofline (DESIGN/EXPERIMENTS §Roofline).

Hardware model (TPU v5e target):
  peak bf16 compute   197 TFLOP/s per chip
  HBM bandwidth       819 GB/s per chip
  ICI link bandwidth  ~50 GB/s per chip
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(bf16|f64|f32|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    wire_bytes: dict            # per-chip estimated wire traffic by op kind

    @property
    def total_wire_bytes(self) -> float:
        return float(sum(self.wire_bytes.values()))


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Per-chip wire-byte estimate from post-SPMD HLO.

    Shapes in the partitioned module are per-device local shapes.  Ring
    estimates: all-reduce ≈ 2×operand; all-gather ≈ result − operand ≈ result;
    reduce-scatter ≈ operand; all-to-all / permute ≈ operand.
    """
    counts: dict = {}
    wire: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        shapes = _SHAPE_RE.findall(line)
        if not shapes:
            continue
        # result shapes precede the op name; operand shapes follow it
        res_shapes = _SHAPE_RE.findall(line[:m.start(1)])
        opnd_shapes = _SHAPE_RE.findall(line[m.start(1):])
        res_b = sum(_shape_bytes(d, s) for d, s in res_shapes)
        op_b = sum(_shape_bytes(d, s) for d, s in opnd_shapes)
        if kind == "all-reduce":
            b = 2 * op_b
        elif kind == "all-gather":
            b = max(res_b - op_b, res_b // 2)
        elif kind == "reduce-scatter":
            b = op_b
        else:
            b = op_b
        counts[kind] = counts.get(kind, 0) + 1
        wire[kind] = wire.get(kind, 0) + b
    return CollectiveStats(counts, wire)


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    hlo_flops: float            # whole-program FLOPs (all chips)
    hlo_bytes: float            # HBM bytes (all chips)
    wire_bytes_per_chip: float
    model_flops: float          # 6·N·D (train) / 2·N·D (serve), active params
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0

    def finalize(self):
        self.compute_s = self.hlo_flops / (self.n_chips * PEAK_FLOPS)
        self.memory_s = self.hlo_bytes / (self.n_chips * HBM_BW)
        self.collective_s = self.wire_bytes_per_chip / ICI_BW
        return self

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_frac(self) -> float:
        return self.model_flops / max(self.hlo_flops, 1.0)

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops, "hlo_flops": self.hlo_flops,
            "useful_frac": self.useful_flops_frac,
        }


def cost_terms(compiled, n_chips: int):
    """(flops, bytes) from compiled.cost_analysis().

    XLA:CPU reports per-program totals; treat them as whole-program (the
    SPMD program is per-chip → multiply by n_chips for the global count)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    return flops, byts


def active_params(cfg) -> tuple[int, int]:
    """(total, active) parameter counts from the config (analytic): the
    experts this chip holds (all, unless the config holds a share), and
    of them the top_k a token expects (top_k · held / n_experts); shared
    experts, a MoE model's dense layers at their own width, and MLA's
    five projections count as published."""
    d, v = cfg.d_model, cfg.vocab_size
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.mla:
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        qr, kr, vd = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.v_head_dim
        per_attn = (d * qr + qr * h * (nope + rope) + d * (kr + rope)
                    + kr * h * (nope + vd) + h * vd * d)
    else:
        per_attn = d * hd * (h + 2 * kv) + h * hd * d
    glu_mult = 3 if cfg.glu else 2
    per_mlp = glu_mult * d * cfg.d_ff
    per_dense = glu_mult * d * (cfg.d_ff_dense or cfg.d_ff)
    _, held = cfg.held
    shared = cfg.n_shared_experts * per_mlp + d * cfg.n_experts
    per_moe = held * per_mlp + shared
    per_moe_active = (round(cfg.top_k * held / cfg.n_experts * per_mlp)
                      + shared) if cfg.n_experts else 0
    d_in_proj = 2 * cfg.d_inner + 2 * cfg.ssm_state + \
        (cfg.d_inner // cfg.ssm_head_dim if cfg.ssm_state else 0)
    per_mamba = d * d_in_proj + cfg.d_inner * d if cfg.ssm_state else 0

    total = active = v * d
    if not cfg.tie_embeddings and not cfg.n_classes:    # the LM head
        total += v * d
        active += v * d
    pattern = cfg.layer_pattern
    if cfg.is_encoder_decoder:
        pattern = pattern + ("attn",) * cfg.n_encoder_layers
    for kind in pattern:
        if kind == "mamba":
            total += per_mamba
            active += per_mamba
        elif kind in ("moe", "local_moe"):
            total += per_attn + per_moe
            active += per_attn + per_moe_active
        else:
            extra = per_attn if kind != "dec" else 2 * per_attn
            total += extra + per_dense
            active += extra + per_dense
    return total, active


def _attn_instances(cfg, shape):
    """(sq, sk, window, count, kind) for every chunked-attention site."""
    out = []
    s = shape.seq_len
    dec_s = max(s // 4, 8) if cfg.is_encoder_decoder else s
    if cfg.modality == "vision":
        dec_s = s                      # prefix embeds + tokens = seq_len
    full = sum(1 for k in cfg.layer_pattern if k in ("attn", "moe", "dec"))
    local = sum(1 for k in cfg.layer_pattern
                if k in ("local", "local_moe")
                or (k == "shared_attn" and cfg.sliding_window))
    shared_full = sum(1 for k in cfg.layer_pattern
                      if k == "shared_attn" and not cfg.sliding_window)
    if full + shared_full:
        out.append((dec_s, dec_s, 0, full + shared_full, "self"))
    if local:
        out.append((dec_s, dec_s, cfg.sliding_window, local, "self"))
    if cfg.is_encoder_decoder:
        out.append((s, s, 0, cfg.n_encoder_layers, "enc"))
        out.append((dec_s, s, 0, cfg.n_layers, "cross"))
    return out


def scan_interior_correction(cfg, shape) -> tuple[float, float]:
    """(flops_add, bytes_add), global across chips.

    XLA cost_analysis counts a scan body once; the flash-attention chunk
    loops (models/attention.py::_chunked) are scans, so their interiors are
    under-counted by (n_q·n_kv − 1).  This adds back the missing chunk-pair
    costs analytically (exact arithmetic for the matmuls; softmax byte
    traffic modeled as ~8 f32 passes over the score tile).  Validated against
    a fully-unrolled lowering on small shapes in tests/test_roofline.py.
    """
    from repro.models.attention import chunks_for
    if shape.kind == "decode":
        return 0.0, 0.0                    # decode paths have no chunk scans
    mode_factor = 4.0 if shape.kind == "train" else 1.0   # fwd+remat+bwd
    b = shape.global_batch
    kvh, g, hd = cfg.n_kv_heads, max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1), cfg.head_dim
    fl_add = by_add = 0.0
    for sq, sk, window, count, kind in _attn_instances(cfg, shape):
        if not count or sq <= 2048 and (kind != "cross" or sk <= 4096):
            continue
        cq, _ = chunks_for(sq)
        _, ckv = chunks_for(sk)
        if window:
            span = min(int(np.ceil((cq + window) / ckv)) * ckv, sk)
            pairs_true, ck_eff = sq // cq, span
        else:
            pairs_true, ck_eff = (sq // cq) * (sk // ckv), ckv
        flops_pair = 4.0 * b * kvh * g * cq * ck_eff * hd \
            + 5.0 * b * kvh * g * cq * ck_eff
        bytes_pair = 8.0 * b * kvh * g * cq * ck_eff * 4 \
            + 2.0 * b * ck_eff * kvh * hd * 2 \
            + 10.0 * b * cq * kvh * g * hd * 4
        missing = max(pairs_true - 1, 0)
        fl_add += count * missing * flops_pair * mode_factor
        by_add += count * missing * bytes_pair * mode_factor
    return fl_add, by_add


def flash_kernel_adjustment(cfg, shape) -> tuple[float, float]:
    """(flops_delta, bytes_delta) ≤ 0: swapping the jnp online-softmax path
    for the Pallas flash kernel (kernels/flash_attention.py).

    Bytes: the jnp path moves ~8 f32 passes of every (cq × ckv) score tile
    through HBM; the kernel's HBM traffic is its operands — Q + O once and
    K/V re-streamed per q block.  FLOPs: the kernel skips fully-masked causal
    tiles (~half the block grid).
    """
    from repro.models.attention import chunks_for
    if shape.kind == "decode":
        return 0.0, 0.0
    mode_factor = 4.0 if shape.kind == "train" else 1.0
    b = shape.global_batch
    kvh = cfg.n_kv_heads
    g = max(cfg.n_heads // max(kvh, 1), 1)
    hd = cfg.head_dim
    fl_d = by_d = 0.0
    for sq, sk, window, count, kind in _attn_instances(cfg, shape):
        if not count or sq <= 0:
            continue
        cq, _ = chunks_for(sq)
        _, ckv = chunks_for(sk)
        if window:
            span = min(int(np.ceil((cq + window) / ckv)) * ckv, sk)
            pairs, ck_eff = sq // cq, span
        else:
            pairs, ck_eff = (sq // cq) * (sk // ckv), ckv
        # jnp-path totals (same byte model as scan_interior_correction)
        jnp_bytes = pairs * (8.0 * b * kvh * g * cq * ck_eff * 4
                             + 2.0 * b * ck_eff * kvh * hd * 2
                             + 10.0 * b * cq * kvh * g * hd * 4)
        jnp_flops = pairs * (4.0 * b * kvh * g * cq * ck_eff * hd
                             + 5.0 * b * kvh * g * cq * ck_eff)
        # kernel: Q+O once, K/V per q-block sweep; live causal tiles ≈ ½
        bqk = min(512, sq)
        nq = sq // bqk
        kern_bytes = (2.0 * b * kvh * g * sq * hd * 2          # Q + O
                      + 2.0 * b * kvh * sk * hd * 2 * nq)      # K,V streams
        live = 0.5 + 0.5 / max(pairs, 1) if (not window and kind != "enc"
                                             and kind != "cross") else 1.0
        kern_flops = jnp_flops * (live if not window else
                                  min(1.0, (cq + window) / (2 * ck_eff) + 0.5))
        fl_d += count * (kern_flops - jnp_flops) * mode_factor
        by_d += count * (kern_bytes - jnp_bytes) * mode_factor
    return fl_d, by_d


def model_flops(cfg, shape) -> float:
    _, active = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    return 2.0 * active * shape.global_batch          # decode: 1 token
