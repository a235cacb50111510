"""Operations and bytes the algorithms require, from shapes alone. The
program's own counters (``GPT2Config.flops_per_token``, ``cost_analysis``)
are not read: the first drops the head and counts attention as if it were
not causal, the second counts recomputed operations.

``s`` is the dict ``builders/<family>.sizes(cfg)`` returns.
"""


def train_flops_per_token(s, seq_len):
    """Forward + backward of one token at sequence length ``seq_len``:

        6 * (block matmul parameters)      4 D^2 + 2 D F per layer
      + 6 * V * D                          the tied head (published V)
      + 6 * L * D * T                      causal attention: QK^T and PV
                                           are 2*T*D each per token when
                                           full, half that when causal;
                                           backward costs twice forward

    Recomputation (remat, flash's backward recompute of the scores) is not
    counted: this is what the passes require, for MFU."""
    D, F, L, V = s["d_model"], s["d_ff"], s["n_layer"], s["vocab_size"]
    block = 4 * D * D + 2 * D * F
    return 6 * L * block + 6 * V * D + 6 * L * D * seq_len


def forward_flops_per_token(s, context):
    """Forward pass of one token that attends to ``context`` positions."""
    D, F, L, V = s["d_model"], s["d_ff"], s["n_layer"], s["vocab_size"]
    return 2 * L * (4 * D * D + 2 * D * F) + 2 * V * D + 4 * L * D * context


def roofline_s(flops, bytes_moved, peaks):
    """Least seconds the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")


def flash_fwd(batch, s, seq_len, itemsize=2):
    """One causal flash-attention forward call over (batch, H, T, hd):
    QK^T and PV, half of each under the causal mask; reads q, k, v and
    writes o once."""
    H, hd, T = s["n_head"], s["d_head"], seq_len
    flops = 2 * batch * H * T * T * hd          # 2 matmuls * 2*T*T*hd / 2
    return flops, 4 * batch * H * T * hd * itemsize


def flash_bwd(batch, s, seq_len, itemsize=2):
    """The backward call: five matmuls (the scores again, dV, dP, dQ, dK),
    causal; reads q, k, v, o, do and writes dq, dk, dv."""
    H, hd, T = s["n_head"], s["d_head"], seq_len
    flops = 5 * batch * H * T * T * hd
    return flops, 8 * batch * H * T * hd * itemsize


def paged_decode(kv_tokens, s, itemsize=2):
    """Decode attention that reads ``kv_tokens`` cached positions in all
    (summed over sequences and steps), per layer: K and V of every KV head
    once; 4*hd operations per head and cached position."""
    H, Hkv, hd = s["n_head"], s["n_kv_head"], s["d_head"]
    return 4 * kv_tokens * H * hd, 2 * kv_tokens * Hkv * hd * itemsize


def paged_prefill(prompt_len, s, itemsize=2):
    """Causal attention of a whole prompt however it is chunked, per
    layer: 2*P^2*hd per head; K and V written and read once, q and o
    once."""
    H, Hkv, hd, P = s["n_head"], s["n_kv_head"], s["d_head"], prompt_len
    flops = 2 * H * P * P * hd
    return flops, (2 * H + 2 * Hkv) * P * hd * itemsize
