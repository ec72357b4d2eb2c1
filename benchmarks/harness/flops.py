"""Matmul FLOPs one fused-ensemble call needs, from shapes alone.

Copied from ``bench.py`` ``_ensemble_matmul_flops`` (2*M*N*K per matmul).
The text branch dominates; LSTM and GNN are counted; the tree and
isolation-forest branches are gather/compare programs with no matmul FLOPs
in the form deployed (``QuantSettings`` off), recorded as 0.
"""

from __future__ import annotations

from typing import Dict


def ensemble_matmul_flops(*, hidden: int, intermediate: int, layers: int,
                          text_len: int, seq_len: int = 10,
                          feature_dim: int = 64, fanout: int = 16,
                          node_dim: int = 16, batch: int = 1
                          ) -> Dict[str, float]:
    h, i_, l_, t = hidden, intermediate, layers, text_len
    per_tok_layer = 2 * (4 * h * h + 2 * h * i_)      # qkv+o, ffn up+down
    attn = 2 * 2 * t * t * h                          # scores + weighted sum
    bert = l_ * (t * per_tok_layer + attn) + t * 2 * h * h   # + head
    lstm_h = 128
    lstm = seq_len * 2 * (feature_dim + lstm_h) * 4 * lstm_h
    gnn = 2 * (2 * fanout * node_dim * 64 + 3 * 64 * 64)     # rough, tiny
    return {
        "bert_text": float(batch * bert),
        "lstm_sequential": float(batch * lstm),
        "graph_neural": float(batch * gnn),
        "xgboost": 0.0,
        "isolation_forest": 0.0,
        "total": float(batch * (bert + lstm + gnn)),
    }
