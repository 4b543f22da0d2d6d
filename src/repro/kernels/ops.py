"""jit'd wrappers dispatching Pallas kernels vs XLA reference paths.

``use_pallas`` selects the kernel path; the default is the XLA reference.
A kernel runs in Pallas interpret mode (correctness only, any backend)
only when the caller passes ``interpret=True``.
"""
from __future__ import annotations

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.moe_gmm import gmm as _gmm_pallas
from repro.kernels.segment_sum import segment_sum as _segsum_pallas
from repro.kernels.ssd import ssd as _ssd_pallas


def flash_attention(q, k, v, *, causal=True, use_pallas=False,
                    interpret=False):
    if use_pallas:
        return _flash_pallas(q, k, v, causal=causal, interpret=interpret)
    return ref.attention_ref(q, k, v, causal=causal)


def decode_attention(q, k, v, kv_len, *, use_pallas=False, interpret=False):
    if use_pallas:
        return _decode_pallas(q, k, v, kv_len, interpret=interpret)
    return ref.decode_attention_ref(q, k, v, kv_len)


def ssd(x, dt, A, Bm, Cm, *, chunk=256, use_pallas=False, interpret=False):
    if use_pallas:
        return _ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
    return ref.ssd_ref(x, dt, A, Bm, Cm)


def gmm(x, w, *, use_pallas=False, interpret=False):
    if use_pallas:
        return _gmm_pallas(x, w, interpret=interpret)
    return ref.gmm_ref(x, w)


def segment_sum(values, seg_ids, n_segments, *, use_pallas=False,
                interpret=False):
    if use_pallas:
        return _segsum_pallas(values, seg_ids, n_segments, interpret=interpret)
    return ref.segment_sum_ref(values, seg_ids, n_segments)
