"""Kernel-level benchmark: fused spec-verify vs the two-launch composition.

Three row families, all committed as ``BENCH_kernels.json``:

``kernels/kv/{fp32,int8}``
    Paged-KV residency accounting straight from ``PagedKVPool`` (no model):
    bytes/token and bytes/session at the reference serving geometry.  The
    int8 row must show >= 1.5x lower bytes/session than fp32 — that ratio
    is the headline claim of the quantized pool and the CI bench-diff
    keeps it pinned.

``kernels/verify/{composed,fused,fused_int8}``
    A deterministic HBM-traffic model of one verify round (B sessions,
    K drafts) on the v5e roofline (``repro.roofline.hw.HBM_BW``):

    * composed — two launches (paged decode attention + logits, then the
      accept/reject scan) with the [B, K+1, V] logits tensor making a
      full HBM round trip between them;
    * fused — one launch (``spec_verify_fused``): logits live in VMEM
      tile-by-tile and never touch HBM;
    * fused_int8 — the fused launch reading int8 pages + f32 page params.

    ``tokens_per_s`` and ``bw_frac`` are modeled (bytes / HBM_BW + launch
    overhead), so the rows are bit-reproducible on every host.  The CSV
    additionally reports live interpret-mode wall-clock for the same
    shapes (measured-vs-achievable bandwidth); those lines are diagnostic
    and deliberately NOT part of the committed JSON.

``kernels/shard/spec_verify/{1,2,4}``
    The tensor-parallel fused verify (``repro.sharding.spec_verify``) at
    1/2/4 shards: per-shard HBM + ICI all-gather traffic on the same
    roofline, modeled tokens/s, and the pool's resident bytes per shard.
"""

from __future__ import annotations

import time
from typing import List, Tuple

from .common import csv_row

# Reference serving geometry (paper-scale 7B-ish verifier, one edge fleet).
GEOM = dict(
    n_layers=8, n_kv_heads=8, head_dim=128, block_size=16,
    seq=512, batch=8, k_draft=4, vocab=32000,
)
LAUNCH_S = 5e-6  # fixed per-launch dispatch overhead in the model


def _kv_rows() -> Tuple[list, List[str]]:
    from repro.models.paged_kv import PagedKVPool

    rows, lines = [], []
    per_tok = {}
    for mode in ("fp32", "int8"):
        pool = PagedKVPool(
            num_blocks=64,
            block_size=GEOM["block_size"],
            n_layers=GEOM["n_layers"],
            n_kv_heads=GEOM["n_kv_heads"],
            head_dim=GEOM["head_dim"],
            quantize=None if mode == "fp32" else "int8",
        )
        per_tok[mode] = pool.bytes_per_token
        per_session = pool.bytes_per_token * GEOM["seq"]
        rows.append(dict(
            name=f"kernels/kv/{mode}",
            bytes_per_token=pool.bytes_per_token,
            bytes_per_session=per_session,
        ))
        lines.append(csv_row(
            f"kernels/kv/{mode}", 0.0,
            f"bytes_per_token={pool.bytes_per_token};bytes_per_session={per_session}",
        ))
    ratio = per_tok["fp32"] / per_tok["int8"]
    rows.append(dict(name="kernels/kv/ratio", fp32_over_int8=round(ratio, 4)))
    lines.append(csv_row("kernels/kv/ratio", 0.0, f"fp32_over_int8={ratio:.2f}x"))
    assert ratio >= 1.5, f"int8 pool must cut bytes/session >=1.5x (got {ratio:.2f})"
    return rows, lines


def _verify_traffic(variant: str) -> dict:
    """HBM bytes moved by one verify round, per the kernel's access pattern."""
    L1 = 1  # the verify launch touches one layer's pages (layer-0 serving KV)
    H, hd, bs = GEOM["n_kv_heads"], GEOM["head_dim"], GEOM["block_size"]
    B, K1, V = GEOM["batch"], GEOM["k_draft"] + 1, GEOM["vocab"]
    F = H * hd
    n_pages = -(-GEOM["seq"] // bs)
    kv_elt = 1 + 8 / hd if "int8" in variant else 4  # int8 payload + f32 params
    kv = 2 * L1 * B * n_pages * bs * H * hd * kv_elt  # K and V page streams
    q = B * K1 * F * 4
    w = B * F * V * 4  # LM-head tile stream, no cross-batch reuse in-kernel
    o = B * K1 * F * 4  # attention output
    logits_hbm = 2 * B * K1 * V * 4  # write + read between the two launches
    launches = 1 if variant.startswith("fused") else 2
    if launches == 1:
        total = kv + q + w + 2 * 4 * B * K1  # outputs: n_acc/corr + logp
    else:
        total = kv + q + w + 2 * o + logits_hbm + 2 * 4 * B * K1
    return dict(bytes=int(total), launches=launches)


def _verify_rows() -> Tuple[list, List[str]]:
    from repro.roofline.hw import HBM_BW

    rows, lines = [], []
    B, K1 = GEOM["batch"], GEOM["k_draft"] + 1
    base_time = None
    for variant in ("composed", "fused", "fused_int8"):
        m = _verify_traffic(variant)
        t = m["bytes"] / HBM_BW + m["launches"] * LAUNCH_S
        bw_frac = (m["bytes"] / t) / HBM_BW
        tok_s = B * K1 / t
        if base_time is None:
            base_time = t
        rows.append(dict(
            name=f"kernels/verify/{variant}",
            launches=m["launches"],
            hbm_bytes=m["bytes"],
            modeled_us=round(t * 1e6, 3),
            tokens_per_s=round(tok_s, 1),
            bw_frac=round(bw_frac, 4),
            speedup_vs_composed=round(base_time / t, 4),
        ))
        lines.append(csv_row(
            f"kernels/verify/{variant}", t * 1e6,
            f"launches={m['launches']};bytes={m['bytes']};"
            f"tokens_per_s={tok_s:.0f};bw_frac={bw_frac:.3f};"
            f"speedup={base_time / t:.2f}x",
        ))
    return rows, lines


def _shard_rows() -> Tuple[list, List[str]]:
    """Modeled roofline for the SHARDED fused verify at 1/2/4 shards.

    Per-shard HBM traffic divides along the head axis (KV pages and
    queries; the reference 8 kv heads split 1/2/4 evenly) and the vocab
    axis (LM-head tile stream).  Keeping the ONE-launch contract across
    shards adds two all-gathers on the ICI — attention outputs [B, K1, F]
    after the head split and per-shard logits tiles [B, K1, V/N] after the
    vocab split — modeled as ring traffic at ``ICI_LINK_BW``.  Resident
    bytes/shard comes straight from ``PagedKVPool.resident_bytes_per_shard``
    on the reference serving pool, so the committed rows pin both the
    throughput scaling AND the per-device memory win.
    """
    from repro.models.paged_kv import PagedKVPool
    from repro.roofline.hw import HBM_BW, ICI_LINK_BW

    H, hd, bs = GEOM["n_kv_heads"], GEOM["head_dim"], GEOM["block_size"]
    B, K1, V = GEOM["batch"], GEOM["k_draft"] + 1, GEOM["vocab"]
    F = H * hd
    n_pages = -(-GEOM["seq"] // bs)
    pool = PagedKVPool(
        num_blocks=64, block_size=bs, n_layers=GEOM["n_layers"],
        n_kv_heads=H, head_dim=hd,
    )
    pool.create(0)
    pool.append(0, GEOM["seq"])  # one reference resident session
    rows, lines = [], []
    t1 = None
    prev_tok_s = 0.0
    for n in (1, 2, 4):
        assert pool.shard_axes(n), "reference geometry must split evenly"
        kv = 2 * B * n_pages * bs * H * hd * 4 // n  # local head slice
        q = B * K1 * F * 4 // n
        w = B * F * V * 4 // n  # per-shard vocab tiles
        out = 2 * 4 * B * K1  # replicated n_acc/corr + logp
        hbm = kv + q + w + out
        gather = (B * K1 * F * 4 * (n - 1)) // n  # head all-gather (ring)
        gather += B * K1 * (V // n) * 4 * (n - 1)  # vocab all-gather
        t = hbm / HBM_BW + gather / ICI_LINK_BW + LAUNCH_S  # still ONE launch
        t1 = t if t1 is None else t1
        tok_s = B * K1 / t
        resident = pool.resident_bytes_per_shard(n)
        rows.append(dict(
            name=f"kernels/shard/spec_verify/{n}",
            shards=n,
            launches=1,
            hbm_bytes_per_shard=hbm,
            ici_bytes_per_shard=gather,
            resident_bytes_per_shard=resident,
            modeled_us=round(t * 1e6, 3),
            tokens_per_s=round(tok_s, 1),
            speedup_vs_1shard=round(t1 / t, 4),
        ))
        lines.append(csv_row(
            f"kernels/shard/spec_verify/{n}", t * 1e6,
            f"shards={n};hbm_bytes={hbm};ici_bytes={gather};"
            f"resident_bytes_per_shard={resident};tokens_per_s={tok_s:.0f};"
            f"speedup={t1 / t:.2f}x",
        ))
        assert tok_s > prev_tok_s, "sharding must not lose modeled throughput"
        prev_tok_s = tok_s
    return rows, lines


def _measured_lines() -> List[str]:
    """Live interpret-mode timing: measured vs achievable bandwidth.

    Small geometry (interpret mode is a CPU emulator); the point is the
    measured-GB/s column next to the 819 GB/s roofline, not the absolute
    numbers.  Not committed — wall-clock is host-dependent.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.spec_verify import fused_target_logits, spec_verify, spec_verify_fused
    from repro.roofline.hw import HBM_BW

    B, K, H, hd, bs, NB, V = 2, 3, 2, 16, 4, 8, 256
    K1, F = K + 1, H * hd
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    k_pages = jax.random.normal(ks[0], (NB, bs, H, hd), jnp.float32)
    v_pages = jax.random.normal(ks[1], (NB, bs, H, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, K1, H, hd), jnp.float32)
    w = jax.random.normal(ks[3], (F, V), jnp.float32) * 4
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    base = np.asarray([5, 7])
    lengths = jnp.asarray(base[:, None] + np.arange(K1)[None, :], jnp.int32)
    toks = jax.random.randint(ks[4], (B, K), 0, V, jnp.int32)
    nd = jnp.full((B,), K, jnp.int32)

    def _fused():
        return spec_verify_fused(
            q, k_pages, v_pages, w, tables, lengths, toks, nd,
            impl="interpret", block_v=256,
        )

    def _composed():
        o = paged_decode_attention(
            q.reshape(B * K1, H, hd), k_pages, v_pages,
            jnp.repeat(tables, K1, axis=0), lengths.reshape(-1), impl="interpret",
        ).reshape(B, K1, H, hd).astype(jnp.float32)
        logits = fused_target_logits(o, w, block_v=256, v_true=V)
        return spec_verify(logits, toks, nd, impl="interpret", block_v=256)

    na_f, _, _ = _fused()
    na_c, _, _ = _composed()
    np.testing.assert_array_equal(np.asarray(na_f), np.asarray(na_c))

    approx_bytes = (k_pages.nbytes + v_pages.nbytes + q.nbytes + B * w.nbytes)
    lines = []
    for name, fn in (("fused", _fused), ("composed", _composed)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        dt = time.perf_counter() - t0
        gbs = approx_bytes / dt / 1e9
        lines.append(csv_row(
            f"kernels/measured/{name}", dt * 1e6,
            f"interpret;measured_GBps={gbs:.3f};achievable_GBps={HBM_BW / 1e9:.0f};"
            f"frac={gbs / (HBM_BW / 1e9):.2e}",
        ))
    return lines


def kernels() -> Tuple[list, List[str]]:
    """Harness entry (benchmarks.run): committed rows + diagnostic CSV."""
    kv_rows, kv_lines = _kv_rows()
    v_rows, v_lines = _verify_rows()
    s_rows, s_lines = _shard_rows()
    return kv_rows + v_rows + s_rows, kv_lines + v_lines + s_lines + _measured_lines()
