"""Training `correct`, against the plain reference on the same seeded weights.

Two comparisons, both after the window, when the train state has been freed:

1. **The gradient, element by element** (the precision check; its control
   fails). The program's model code (``transformer.loss_fn`` under
   ``value_and_grad``, compute dtype, flash kernel and sharding as in the
   train step) and the reference each give the gradient of the mean loss of
   the first ``check_sequences`` sequences of batch 0. Compared:
   ``||g_program - g_reference|| / ||g_reference||`` over every parameter.
   A norm of a difference keeps per-element rounding noise that a difference
   of norms averages away: the int8 control reads *below* the bf16 program
   on the gradient norm and the loss, and well above it here.
2. **Step 1 of the real train step** (a check of meaning, not of precision):
   the ``loss`` and ``grad_norm`` that the step program returns for the whole
   first batch, against the reference's. Microbatching, clipping's norm and
   the batch layout are in it; a lower precision is not caught by it.

Limits: ``grad_rel_err`` is stated by each configuration file
(``check_limits``), from that configuration's own readings on the chip;
PERF.md section 2 gives the readings each limit stands between.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Tuple

import numpy as np

LIMITS = {"loss_rel_err": 2e-4, "grad_norm_rel_err": 2e-2}  # grad_rel_err: stated by the configuration


class Reference:
    """The reference's weights, spread over ``devices`` (placement only: each
    array on its last axis where it divides, one sequence per device per
    microbatch), and the two things read from it."""

    def __init__(self, arch: Dict[str, Any], seed: int, devices: Any) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from harness import opcount, weights

        self.arch, self.n = arch, len(devices)
        self.ref = importlib.import_module(f"references.{arch['family']}")
        self.mesh = Mesh(np.asarray(devices), ("x",))
        n_layers = opcount.dims(arch)["layers"]

        def make(key):
            return {
                "layers": jax.lax.map(lambda l: weights.layer(arch, key, l, jnp.float32),
                                      jnp.arange(n_layers)),
                "globals": weights.globals_(arch, key, jnp.float32),
            }

        def place(a):
            spec = [None] * a.ndim
            if a.ndim >= 2 and a.shape[-1] % self.n == 0:
                spec[-1] = "x"
            return NamedSharding(self.mesh, P(*spec))

        key = weights.seed_key(seed)
        self.w_sh = jax.tree.map(place, jax.eval_shape(make, key))
        self.d_sh = NamedSharding(self.mesh, P(None, "x", None))
        self.w = jax.jit(make, out_shardings=self.w_sh)(key)

    def _batch(self, x: np.ndarray, y: np.ndarray):
        import jax
        import jax.numpy as jnp

        if x.shape[0] % self.n:
            raise ValueError("sequences must divide by the device count")
        xs = jnp.asarray(x.reshape(x.shape[0] // self.n, self.n, -1))
        return jax.device_put(xs, self.d_sh), jax.device_put(jnp.asarray(y.reshape(xs.shape)), self.d_sh)

    def loss_and_grads(self, x: np.ndarray, y: np.ndarray, quant: Any = None):
        """(loss, gradient tree in the canonical layout) of sequences ``x, y``."""
        import jax

        fn = jax.jit(lambda w, a, b: self.ref.loss_and_grads(w, a, b, self.arch, quant),
                     in_shardings=(self.w_sh, self.d_sh, self.d_sh), out_shardings=(None, self.w_sh))
        return fn(self.w, *self._batch(x, y))

    def loss_and_grad_norm(self, x: np.ndarray, y: np.ndarray, quant: Any = None) -> Tuple[float, float]:
        import jax

        def both(w, a, b):
            loss, g = self.ref.loss_and_grads(w, a, b, self.arch, quant)
            return loss, self.ref.global_norm(g)

        loss, gn = jax.jit(both, in_shardings=(self.w_sh, self.d_sh, self.d_sh))(self.w, *self._batch(x, y))
        return float(loss), float(gn)


def program_grads(arch: Dict[str, Any], seed: int, cfg: Any, mesh: Any, x: np.ndarray, y: np.ndarray):
    """Gradient of the program's own loss on ``x, y`` at the seeded weights,
    as a tree in the program's layout (sharded as the train step shards it)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from harness import weights
    from pretraining_llm_tpu.models import transformer
    from pretraining_llm_tpu.parallel.sharding import (
        activation_mesh, batch_pspec, named_sharding_tree, param_pspec_tree)

    make = lambda key: weights.program_params(arch, key, jnp.dtype(cfg.model.param_dtype))
    grad = jax.value_and_grad(lambda p, a, b: transformer.loss_fn(p, a, b, cfg.model))
    key = weights.seed_key(seed)
    if mesh is None:
        return jax.jit(lambda k, a, b: grad(make(k), a, b)[1])(key, jnp.asarray(x), jnp.asarray(y))
    p_sh = named_sharding_tree(mesh, param_pspec_tree(jax.eval_shape(make, key), False, tensor_size=1))
    b_sh = NamedSharding(mesh, batch_pspec(cfg.model.sequence_parallel))

    def fn(p, a, b):
        with activation_mesh(mesh):
            return grad(p, a, b)[1]

    params = jax.jit(make, out_shardings=p_sh)(key)
    xb, yb = jax.device_put((jnp.asarray(x), jnp.asarray(y)), (b_sh, b_sh))
    return jax.jit(fn, in_shardings=(p_sh, b_sh, b_sh), out_shardings=p_sh)(params, xb, yb)


def grad_rel_err(arch: Dict[str, Any], g_program: Any, g_reference: Any) -> float:
    """||g_program - g_reference|| / ||g_reference||, the reference's canonical
    tree first put into the program's layout (the same pure reshapes as the weights)."""
    import jax
    import jax.numpy as jnp

    from harness import weights

    def err(gp, gr):
        ref = weights.program_tree(arch, gr["layers"], gr["globals"])
        sq = lambda t: sum(jnp.sum(jnp.square(a.astype(jnp.float32))) for a in jax.tree.leaves(t))
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, gp, ref)
        return jnp.sqrt(sq(diff) / sq(ref))

    return float(jax.jit(err)(g_program, g_reference))


def compare(arch: Dict[str, Any], seed: int, cfg: Any, mesh: Any, devices: Any,
            x: np.ndarray, y: np.ndarray, step1: Tuple[float, float], n_check: int,
            log: Any = print) -> Dict[str, Tuple[float, float]]:
    """All three numbers beside their limits. ``x, y``: batch 0; ``step1``: the
    train step's (loss, grad_norm) for it."""
    ref = Reference(arch, seed, devices)
    g_prog = program_grads(arch, seed, cfg, mesh, x[:n_check], y[:n_check])
    _, g_ref = ref.loss_and_grads(x[:n_check], y[:n_check])
    out = {"grad_rel_err": (grad_rel_err(arch, g_prog, g_ref), arch["check_limits"]["grad_rel_err"])}
    del g_prog, g_ref
    r_loss, r_gn = ref.loss_and_grad_norm(x, y)
    log(f"step 1: program loss {step1[0]:.6f} grad_norm {step1[1]:.6f}; "
        f"reference loss {r_loss:.6f} grad_norm {r_gn:.6f}")
    out["loss_rel_err"] = (abs(step1[0] - r_loss) / abs(r_loss), LIMITS["loss_rel_err"])
    out["grad_norm_rel_err"] = (abs(step1[1] - r_gn) / abs(r_gn), LIMITS["grad_norm_rel_err"])
    return out
