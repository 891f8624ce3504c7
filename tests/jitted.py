"""How a model's tests take a value, or a value and its gradients, of the
program or of its plain reference: as ONE compiled program. Outside
``jax.jit`` every operation of a pass and of its transpose is dispatched,
and where new compiled, one by one (PR 31's file held a worker for 447 s
that way, 82% of the suite's wall); a whole program is compiled once and the
persistent cache (``conftest.py``) serves it to every later run."""

import jax

from ray_tpu.models import llama

init_params = jax.jit(llama.init_params, static_argnums=0)
loss_fn = jax.jit(llama.loss_fn, static_argnums=0)
forward = jax.jit(llama.forward, static_argnums=0)


def reference(fn, *args):
    """``fn(*args)`` of the plain reference, compiled as one program at the
    precision the reference is defined at."""
    def at_highest(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    return jax.jit(at_highest)(*args)


def value_and_grad(fn, *args, **kw):
    """``jax.value_and_grad(fn, **kw)(*args)``, compiled as one program."""
    return jax.jit(jax.value_and_grad(fn, **kw))(*args)
