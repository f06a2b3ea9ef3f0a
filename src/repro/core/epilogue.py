"""The GEE epilogue: one numerics source of truth.

Every backend ends the same way -- fold the diagonal-augmentation term,
apply the Laplacian degree scaling, row-L2-normalize under the
"correlation" option -- yet the repo grew five divergent copies of that
arithmetic (``repro.core.gee._row_l2_normalize``, the Pallas
``repro.kernels.row_norm`` kernel, the chunked ``_finalize``, the
incremental path's host-side renorm, and a SciPy variant with its own
``1e-300`` epsilon).  This module is the single home; the copies are now
thin delegates, so the numerics cannot drift again.

Conventions (shared by every backend, tested cross-backend to <= 1e-5):

* ``EPS_NORM = 1e-30``: a row with norm > 0 is divided by
  ``max(norm, EPS_NORM)``; exact-zero rows (isolated vertices, or rows
  whose neighbors are all unlabeled) stay exactly zero.
* Degrees invert the same way: ``d > 0 -> rsqrt(max(d, EPS_NORM))``,
  0 otherwise.
* ``impl="auto"`` routes the row normalization to the Pallas
  ``row_norm`` kernel on a TPU; on CPU the kernel would run in interpret
  mode, strictly slower than the fused jnp form
  (:func:`repro.kernels.platform.interpret_mode` decides).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# NOTE: this module sits *below* repro.core.gee in the import graph (gee
# delegates its epilogue here), so it must not import it.  ``opts`` is any
# hashable object with the three GEEOptions flags.

# The shared near-zero clamp for row norms and degree inversions.  float32
# cannot represent a nonzero norm below ~1e-38, so 1e-30 only engages on
# denormal-scale rows -- where it caps the blow-up instead of dividing by
# a denormal (the SciPy backend, computing in float64, clamps at the same
# point so all backends agree on such rows).
EPS_NORM = 1e-30

ROW_NORM_IMPLS = ("auto", "jnp", "pallas")


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        from repro.kernels.platform import interpret_mode

        return "jnp" if interpret_mode() else "pallas"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown impl {impl!r}; pick one of "
                         f"{ROW_NORM_IMPLS}")
    return impl


# ---------------------------------------------------------------------------
# row L2 normalization (the "correlation" option)
# ---------------------------------------------------------------------------

def row_l2_normalize_jnp(z: jax.Array, eps: float = EPS_NORM) -> jax.Array:
    """Pure-jnp row normalization; safe inside any jit/shard_map body."""
    norm = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
    return jnp.where(norm > 0, z / jnp.maximum(norm, eps), 0.0)


def row_l2_normalize(z: jax.Array, *, impl: str = "auto",
                     interpret: bool | None = None) -> jax.Array:
    """Row-L2-normalize [N, K]; zero rows stay zero.

    ``impl="auto"`` picks the Pallas ``row_norm`` kernel on TPU, the
    fused jnp form on CPU.  ``interpret`` is forwarded to the kernel
    (None = :func:`repro.kernels.platform.interpret_mode`).
    """
    if _resolve_impl(impl) == "pallas":
        from repro.kernels.platform import interpret_mode
        from repro.kernels.row_norm import row_norm  # deferred: no cycle

        if interpret is None:
            interpret = interpret_mode()
        return row_norm(z, eps=EPS_NORM, interpret=interpret)
    return row_l2_normalize_jnp(z)


def row_l2_normalize_np(z: np.ndarray, eps: float = EPS_NORM) -> np.ndarray:
    """Host-side (numpy, any float dtype) twin of ``row_l2_normalize``."""
    z = np.asarray(z)
    norm = np.sqrt((z * z).sum(axis=-1, keepdims=True))
    out = np.zeros_like(z)
    np.divide(z, np.maximum(norm, eps), out=out, where=norm > 0)
    return out


# ---------------------------------------------------------------------------
# degree inversion (the Laplacian scaling)
# ---------------------------------------------------------------------------

def inv_sqrt_degrees(deg: jax.Array, eps: float = EPS_NORM) -> jax.Array:
    """d -> d^{-1/2} with the shared zero-degree convention (0 -> 0)."""
    return jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, eps)), 0.0)


def inv_sqrt_degrees_np(deg: np.ndarray,
                        eps: float = EPS_NORM) -> np.ndarray:
    """Host-side twin of ``inv_sqrt_degrees`` (float64 accumulators)."""
    deg = np.asarray(deg)
    return np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, eps)), 0.0)


# ---------------------------------------------------------------------------
# the full O(N*K) epilogue (diag-aug term + correlation)
# ---------------------------------------------------------------------------

def diag_aug_epilogue(z: jax.Array, labels: jax.Array, winv: jax.Array,
                      dinv: jax.Array) -> jax.Array:
    """Fold the self-loop term ``Z[i, y_i] += dinv_i^2 * w / n_{y_i}``.

    This is how streaming backends apply diagonal augmentation without
    ever appending loop edges: ``dinv`` already holds ``d_aug^{-1/2}``
    (all-ones when Laplacian is off), so ``dinv_i^2`` is the
    Laplacian-scaled loop weight.  Unlabeled rows (-1) are untouched.
    """
    n = z.shape[0]
    valid = labels >= 0
    ys = jnp.where(valid, labels, 0)
    add = jnp.where(valid, dinv * dinv * winv[ys], 0.0)
    return z.at[jnp.arange(n), ys].add(add)


def apply_epilogue(z: jax.Array, labels: jax.Array, winv: jax.Array,
                   dinv: jax.Array, *, opts, impl: str = "jnp") -> jax.Array:
    """The whole O(rows*K) epilogue on an already-shaped [rows, K] block.

    This is the single composition every backend tail delegates to --
    ``finalize`` (chunked streaming), ``repro.core.fold.combine_partials``
    (the shard_map row-local tail), and the residual fixup of the fused
    Pallas path (``repro.kernels.gee_fused``) -- so the option order
    (diag-aug, then correlation) and the shared clamps live in exactly
    one place.  ``labels``/``dinv`` are row-aligned with ``z`` (slices
    for a sharded block); ``impl="jnp"`` keeps it safe inside any
    jit/shard_map body.
    """
    if opts.diag_aug:
        z = diag_aug_epilogue(z, labels, winv, dinv)
    if opts.correlation:
        z = row_l2_normalize(z, impl=impl)
    return z


@partial(jax.jit, static_argnames=("num_classes", "opts", "impl"))
def finalize(z_flat: jax.Array, labels: jax.Array, winv: jax.Array,
             dinv: jax.Array, *, num_classes: int, opts,
             impl: str = "jnp") -> jax.Array:
    """Apply the O(N*K) epilogue once: diag-aug self loops, correlation.

    ``z_flat`` is the [N*K] (or [N, K]) pre-epilogue accumulator;
    ``dinv`` is all-ones when Laplacian normalization is off (``w * 1.0``
    is exact in float32, so the no-Laplacian path stays bit-faithful).
    """
    n = dinv.shape[0]
    z = z_flat.reshape(n, num_classes)
    return apply_epilogue(z, labels, winv, dinv, opts=opts, impl=impl)


__all__ = ["EPS_NORM", "ROW_NORM_IMPLS", "row_l2_normalize",
           "row_l2_normalize_jnp", "row_l2_normalize_np",
           "inv_sqrt_degrees", "inv_sqrt_degrees_np", "diag_aug_epilogue",
           "apply_epilogue", "finalize"]
