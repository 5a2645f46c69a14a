"""Kernel-region scoping that survives autodiff.

``jax.named_scope`` metadata is lost on ops produced by transpose/jvp
rewrites, so backward passes of kernel regions would lose the scope that
trace readers charge their time to. ``scoped_kernel_vjp`` wraps a region in a ``custom_vjp`` whose
backward re-traces the region *inside* a scope — which is also the faithful
model of the real TPU execution: a Pallas forward kernel plus a
recompute-based backward kernel (flash-attention-style)."""

from __future__ import annotations

import jax


def scoped_kernel_vjp(scope: str, fn):
    """Wrap ``fn(*arrays) -> pytree`` so both passes carry ``scope`` metadata.

    The backward recomputes the forward (checkpoint semantics — exactly what a
    fused attention/SSD backward kernel does on TPU)."""

    @jax.custom_vjp
    def wrapped(*args):
        with jax.named_scope(scope):
            return fn(*args)

    def fwd(*args):
        with jax.named_scope(scope):
            return fn(*args), args

    def bwd(res, g):
        with jax.named_scope(scope + "_bwd"):
            _, vjp = jax.vjp(fn, *res)
            return vjp(g)

    wrapped.defvjp(fwd, bwd)
    return wrapped
