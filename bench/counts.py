"""Operations and bytes that one verify dispatch needs, whatever implements it.

Counted from the shapes of the rounds a dispatch carries, never from the
kernel's own grid or padding:

* the LM head once per dispatch, at its unpadded ``[H*hd, V]``;
* each distinct layer-0 KV page any row reads, once: the shared prefix's
  pages once for the whole dispatch, and each session's own pages past it;
* queries (``K_i + 1`` rows of ``[H, hd]``) and outputs (``n_accepted``, the
  correction and ``K_i`` log-probs per session);
* FLOPs of the useful rows only: row ``j`` of a session with base length
  ``b`` attends ``b + j`` positions (``QK^T`` and ``PV``, 2 FLOPs per
  multiply-add each) and takes an ``[H*hd] x [H*hd, V]`` LM-head product.
  No pad rows, pad positions or pad pages.

Bytes are in the dtype the configuration stores (``dtype_bytes``).
"""

from __future__ import annotations

from typing import Dict, Sequence


def dispatch_counts(model: Dict, rows: Sequence[Dict], *, prefix: int, page: int, dtype_bytes: int = 4) -> Dict:
    """``{"flops", "bytes"}`` for one dispatch.

    ``rows`` holds one entry per session in the dispatch with ``k`` (drafts)
    and ``base`` (positions row 0 sees; row ``j`` sees ``base + j``).
    """
    H, Hkv, hd, V = (model["num_attention_heads"], model["num_key_value_heads"],
                     model["head_dim"], model["vocab_size"])
    F = H * hd
    flops = 0
    prefix_pages = -(-prefix // page)
    own_pages = 0
    for r in rows:
        k, base = int(r["k"]), int(r["base"])
        ctx = sum(base + j for j in range(k + 1))
        flops += 2 * 2 * H * hd * ctx + 2 * F * V * (k + 1)
        last = base + k  # positions the last row sees
        own_pages += max(-(-last // page) - prefix_pages, 0)
    page_bytes = 2 * page * Hkv * hd * dtype_bytes  # K and V
    q_bytes = sum(int(r["k"]) + 1 for r in rows) * F * dtype_bytes
    out_bytes = sum(int(r["k"]) + 2 for r in rows) * 4
    nbytes = F * V * dtype_bytes + (prefix_pages + own_pages) * page_bytes + q_bytes + out_bytes
    return {"flops": flops, "bytes": nbytes}


def roofline_seconds(counts: Dict, peaks: Dict) -> Dict:
    """The least time the chip could take, and which bound sets it."""
    t_flops = counts["flops"] / peaks["flops_per_s"]
    t_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "bound": "flops" if t_flops > t_bytes else "bytes"}
