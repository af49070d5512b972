"""The roofline counts against hand-worked values (bench/counts.py)."""

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
import pytest

from bench import counts

MODEL = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 100}


def test_lm_head_counted_once_per_dispatch():
    one = counts.dispatch_counts(MODEL, [{"k": 1, "base": 17}], prefix=16, page=16)
    two = counts.dispatch_counts(MODEL, [{"k": 1, "base": 17}] * 2, prefix=16, page=16)
    head = 4 * 8 * 100 * 4
    page = 2 * 16 * 2 * 8 * 4  # K and V of one page
    # one session: head + prefix page + its own page + 2 query rows + outputs
    assert one["bytes"] == head + 2 * page + 2 * 32 * 4 + 3 * 4
    # a second session adds its own page, queries and outputs, never the head again
    assert two["bytes"] - one["bytes"] == page + 2 * 32 * 4 + 3 * 4


def test_shared_prefix_pages_counted_once():
    rows = [{"k": 2, "base": 33}, {"k": 2, "base": 40}, {"k": 2, "base": 60}]
    c = counts.dispatch_counts(MODEL, rows, prefix=32, page=16)
    head, page = 4 * 8 * 100 * 4, 2 * 16 * 2 * 8 * 4
    # prefix: 2 pages once; own pages: ceil(35/16)-2=1, ceil(42/16)-2=1, ceil(62/16)-2=2
    q = sum(r["k"] + 1 for r in rows) * 32 * 4
    out = sum(r["k"] + 2 for r in rows) * 4
    assert c["bytes"] == head + (2 + 4) * page + q + out


def test_flops_count_useful_rows_only():
    rows = [{"k": 2, "base": 10}]
    c = counts.dispatch_counts(MODEL, rows, prefix=8, page=16)
    F, V, H, hd = 32, 100, 4, 8
    attn = sum(2 * 2 * H * hd * (10 + j) for j in range(3))
    assert c["flops"] == attn + 3 * 2 * F * V
    # a pad row would add a whole LM-head product: the count has none
    c2 = counts.dispatch_counts(MODEL, rows + [{"k": 0, "base": 10}], prefix=8, page=16)
    assert c2["flops"] - c["flops"] == 2 * 2 * H * hd * 10 + 2 * F * V


@pytest.mark.parametrize("flops,nbytes,bound", [(10**10, 10**6, "flops"), (10**3, 10**9, "bytes")])
def test_roofline_names_its_bound(flops, nbytes, bound):
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    r = counts.roofline_seconds({"flops": flops, "bytes": nbytes}, peaks)
    assert r["bound"] == bound
    assert r["seconds"] == max(flops / 1e12, nbytes / 1e9)
