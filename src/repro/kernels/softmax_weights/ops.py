"""Dispatcher for the fused softmax-weights kernel.

Backend resolution happens host-side in the wrapper (not at trace time
inside the jit) so a device switch re-resolves instead of serving a
stale cached choice; see ``repro.kernels.dispatch``.
"""
from __future__ import annotations

from functools import partial

import jax

from ..dispatch import resolve_impl
from .kernel import softmax_weights_pallas
from .ref import softmax_weights_ref


@partial(jax.jit, static_argnames=("sign", "impl", "interpret"))
def _softmax_weights_jit(v, eta, sign: float, impl: str, interpret: bool):
    if impl == "pallas":
        return softmax_weights_pallas(v, eta, sign=sign, interpret=interpret)
    return softmax_weights_ref(v, eta, sign=sign)


def softmax_weights(v, eta, sign: float = 1.0, impl: str = "auto"):
    """(lse, w): lse = logsumexp(sign*eta*v); w = softmax(sign*eta*v).

    smax_eta(v) = lse/eta (sign=+1); smin_eta(v) = -lse/eta (sign=-1).
    impl: "auto" (the dispatch default, xla) | "pallas" | "xla".
    """
    impl, interpret = resolve_impl("softmax", impl, n=v.shape[0], dtype=v.dtype)
    return _softmax_weights_jit(v, eta, sign, impl, interpret)
