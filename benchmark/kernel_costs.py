"""Analytic operations and bytes of the Mosaic kernels in a sequence conf's
step, one call each, from shapes alone: the numerators of the
``kernels.*_roofline_pct`` readers.  What the algorithm needs, not what a
tiling happens to compute (a causal kernel's diagonal blocks compute masked
scores too; they are not counted)."""

from __future__ import annotations


def flash_attention(seq: int, heads: int, dim_qk: int, dim_v: int,
                    batch: int = 1, bytes_each: int = 2) -> dict:
    """Causal attention forward: scores and their product with the values
    for every query and every key up to it; reads q, k, v once, writes o."""
    pairs = seq * (seq + 1) // 2
    return {'flops': 2 * batch * heads * pairs * (dim_qk + dim_v),
            'bytes': batch * heads * seq * (2 * dim_qk + 2 * dim_v)
            * bytes_each}


def flash_attention_dq(seq: int, heads: int, dim_qk: int, dim_v: int,
                       batch: int = 1, bytes_each: int = 2) -> dict:
    """The backward kernel of the queries: the scores again (q k^T), dp =
    do v^T, dq = ds k; reads q, k, v, do (and the row sums), writes dq."""
    pairs = seq * (seq + 1) // 2
    return {'flops': 2 * batch * heads * pairs * (2 * dim_qk + dim_v),
            'bytes': batch * heads * seq * (3 * dim_qk + 2 * dim_v)
            * bytes_each}


def flash_attention_dkv(seq: int, heads: int, dim_qk: int, dim_v: int,
                        batch: int = 1, bytes_each: int = 2) -> dict:
    """The backward kernel of keys and values: the scores again, dp = do
    v^T, dv = p^T do, dk = ds^T q; reads q, k, v, do, writes dk, dv."""
    pairs = seq * (seq + 1) // 2
    return {'flops': 2 * batch * heads * pairs * (2 * dim_qk + 2 * dim_v),
            'bytes': batch * heads * seq * (3 * dim_qk + 3 * dim_v)
            * bytes_each}


def grouped_product(rows: float, k: int, n: int, groups: int,
                    bytes_each: int = 2) -> dict:
    """One grouped product over ``rows`` sorted assignments (the sum of the
    group sizes, not the buffer's rows): ``(rows, k) x (groups, k, n)``.
    Every group's matrix is read once, the rows in and out once."""
    return {'flops': 2 * rows * k * n,
            'bytes': (rows * (k + n) + groups * k * n) * bytes_each}


def roofline_pct(cost: dict, calls: float, ms: float, peaks: dict) -> float:
    """The larger of the two shares of the chip's peak that ``calls`` calls
    of ``cost`` reach in ``ms`` of device time."""
    seconds = ms * 1e-3
    return 100.0 * max(cost['flops'] * calls / peaks['bf16_flops_per_s'],
                       cost['bytes'] * calls / peaks['hbm_bytes_per_s']) \
        / seconds


# --- what a sequence conf's step calls, from its graph -----------------------

def attention_shape(graph) -> dict:
    """The blocked attention's shape in ``graph`` (``references/
    glm_moe_lite.Graph``): every ``mla`` layer has the same."""
    l = graph.of_type('mla')[0]
    return dict(seq=graph.seq, heads=l.geti('nhead'),
                dim_qk=l.geti('qk_nope_head_dim') + l.geti('qk_rope_head_dim'),
                dim_v=l.geti('v_head_dim'))


def attention_calls(graph, recomputed: bool) -> int:
    """Calls a step of one attention kernel: one a layer, and the forward
    kernel once more a layer where the layer is recomputed in the backward
    pass (``layers/sequence.py``: ``mla`` is)."""
    return len(graph.of_type('mla')) * (2 if recomputed else 1)


def attention_roofline(run, kernel: str, cost, recomputed: bool):
    """The reader of an attention kernel's share: ``cost`` (one of the
    functions above) at the conf's shape, times the calls a step, over the
    device time of the events named ``kernel...`` (``scope_times``)."""
    from . import scope_times
    ms = scope_times.kernel_ms(run, kernel)
    if ms is None or not run.peaks:
        return None
    graph = run.feed.graph
    return roofline_pct(cost(**attention_shape(graph)),
                        attention_calls(graph, recomputed), ms, run.peaks)
