"""Operations and bytes of one flash-attention backward call, from shapes.

``flops.py``'s conventions: one multiply-add = 2 operations, causal
attention counted as its lower triangle, recomputation not counted.  The
mathematics has four products — dv = pᵀ·do and dp = do·vᵀ at v's width,
dq = ds·k and dk = dsᵀ·q at q's — twice ``flash_forward_call``'s operations
at the same widths.  The scores a kernel recomputes from the saved
log-sum-exp are its own overhead and are left out, as ``step_mfu_pct.train``
leaves recomputation out, so no reading can pass 100 %.
"""


def flash_backward_call(batch, heads, seq_q, seq_k, d_qk, d_v, itemsize,
                        causal=True):
    """(operations, bytes) of one backward call whose q and k are ``d_qk``
    wide and whose v, o and do are ``d_v`` wide: the four products, one
    read of q, k, v, o, do and of a float32 log-sum-exp a row, one write of
    dq, dk and dv."""
    ops = 2 * batch * heads * seq_q * seq_k * (2 * d_qk + 2 * d_v)
    if causal:
        ops //= 2
    nbytes = itemsize * batch * heads * (
        2 * seq_q * d_qk + 2 * seq_k * d_qk         # q, dq; k, dk
        + 2 * seq_k * d_v + 2 * seq_q * d_v) \
        + 4 * batch * heads * seq_q                 # v, dv; o, do; lse
    return ops, nbytes
