"""The system under test, as the benchmark drives it: the program's model
and its continuous-batching engine (``repro.runtime.serving``), given the
benchmark's weights.  Nothing else of the benchmark imports the program;
a family (``bench/families/``) places the weights in the program's tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import build_model
from repro.runtime.serving import ContinuousBatchingEngine


@jax.jit
def norms_to_program(norms):
    """Norm weights as published (around 1) as the program stores them: an
    offset from 1."""
    return jax.tree.map(lambda w: w - jnp.ones((), w.dtype), norms)


def program_params(family, w: dict, model) -> dict:
    """The benchmark's weights (the family's reference layout) in the
    program's tree, as ``family.program_params`` places them; refused where
    the tree, a shape or a dtype differs from the program's own."""
    params = family.program_params(w, model)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or jax.tree.leaves(
        jax.tree.map(lambda a, b: a.shape != b.shape or a.dtype != b.dtype, want, got)
    ).count(True):
        raise ValueError(f"weights do not match the program's parameters:\n{want}\n{got}")
    return params


def build_engine(family, cfg, w: dict, n_slots: int, max_len: int):
    model = build_model(cfg)
    return ContinuousBatchingEngine(model, program_params(family, w, model), n_slots=n_slots,
                                    max_len=max_len)
