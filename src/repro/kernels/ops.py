"""Jitted public wrappers for the Pallas kernels.

On a CPU backend the kernels execute with ``interpret=True`` - the
kernel body runs in Python exactly as written, which validates the block
logic; on TPU they compile natively.  The choice is made at each call
from the default backend, never at import, so importing this module
touches no device.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.cin import cin_layer as _cin
from repro.kernels.dot_interact import dot_interact as _dot_interact
from repro.kernels.embedding_bag import embedding_bag as _embedding_bag
from repro.kernels.flash_attention import flash_attention as _flash_attention


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _flash_attention(q, k, v, **kw)


def embedding_bag(table, ids, weights=None, **kw):
    kw.setdefault("interpret", _interpret())
    return _embedding_bag(table, ids, weights, **kw)


def dot_interact(feats, **kw):
    kw.setdefault("interpret", _interpret())
    return _dot_interact(feats, **kw)


def target_attention(q, keys, mask, w1, b1, w2, b2, w3, b3, **kw):
    from repro.kernels.target_attention import target_attention as _ta
    kw.setdefault("interpret", _interpret())
    return _ta(q, keys, mask, w1, b1, w2, b2, w3, b3, **kw)


def cin_layer(w, x_prev, x0, **kw):
    kw.setdefault("interpret", _interpret())
    return _cin(w, x_prev, x0, **kw)


def cascade_truncate(p_sorted, clicks_sorted, groups, rows, n3, **kw):
    from repro.kernels.cascade_truncate import compact_truncate_revenue
    kw.setdefault("interpret", _interpret())
    return compact_truncate_revenue(p_sorted, clicks_sorted, groups, rows,
                                    n3, **kw)


# the oracles, re-exported so callers can assert parity in one import
references = ref
