"""Operations and bytes of the grouped matmuls (``jax.lax.ragged_dot``)
that a dropless MoE layer's fine-tune step runs, from the configuration
and the batch alone.

Each layer routes ``rows = tokens x top_k`` assignments.  Each expert
site (``moe.gate``, ``moe.up``: ``d_model -> d_ff_expert``;
``moe.down``: back) runs, with LoRA of rank ``r`` on every expert:

* forward: the base product and the adapter's two, ``x W``, ``x A``,
  ``(x A) B^T``, each a grouped matmul over the rows;
* the same three again where the backward recomputes the layer (the
  train step saves nothing inside a layer);
* backward: the input's gradient through the base, ``g W^T``, and
  through the adapter, ``g B`` then ``(g B) A^T``, and the adapters' own
  gradients, ``x^T (g B)`` and ``(x A)^T g``, contracted over each
  expert's rows.

That is 11 grouped matmuls a site and 33 a layer.  Every operand and
result is bf16; a weight stack is read once a call.
"""
from __future__ import annotations

BF16 = 2


def _call(m: int, k: int, n: int, lhs: int, rhs: int, out: int) -> tuple:
    """(flops, bytes) of one grouped matmul that multiplies ``m x k`` by
    ``k x n`` in total over the groups, reading ``lhs`` and ``rhs``
    elements and writing ``out``."""
    return 2.0 * m * k * n, BF16 * (lhs + rhs + out)


def site_calls(rows: int, m: int, n: int, experts: int, rank: int) -> list:
    """(flops, bytes) of each grouped matmul of one expert site ``m -> n``
    over ``rows`` routed rows in one train step."""
    N, E, r = rows, experts, rank
    forward = [
        _call(N, m, n, N * m, E * m * n, N * n),          # x W
        _call(N, m, r, N * m, E * m * r, N * r),          # x A
        _call(N, r, n, N * r, E * r * n, N * n),          # (x A) B^T
    ]
    backward = [
        _call(N, n, m, N * n, E * m * n, N * m),          # g W^T
        _call(N, n, r, N * n, E * n * r, N * r),          # g B
        _call(N, r, m, N * r, E * m * r, N * m),          # (g B) A^T
        _call(m, N, r, N * m, N * r, E * m * r),          # x^T (g B)
        _call(r, N, n, N * r, N * n, E * r * n),          # (x A)^T g
    ]
    return forward * 2 + backward


def step_calls(c: dict, tokens: int) -> list:
    """(flops, bytes) of every grouped matmul of a train step of
    ``tokens`` tokens on configuration ``c``."""
    d, f = c["d_model"], c["d_ff_expert"]
    rows = tokens * c["top_k"]
    per_layer = [call for m, n in ((d, f), (d, f), (f, d))
                 for call in site_calls(rows, m, n, c["n_experts"],
                                        c["lora_rank"])]
    return per_layer * c["n_layers"]
