"""How a model's tests take a value, or a value and its gradients, of the
program or of its plain reference: as ONE compiled program. Outside
``jax.jit`` every operation of a pass and of its transpose is dispatched,
and where new compiled, one by one (PR 31's file held a worker for 447 s
that way, 82% of the suite's wall); a whole program is compiled once and the
persistent cache (``conftest.py``) serves it to every later run."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama
from ray_tpu.util.metrics import registry

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


def walked_both_ways(make_engine, toks, n, monkeypatch):
    """What ``make_engine()`` serves as the walker takes its stack
    (``llama._segments``: periods and runs scanned) and with EVERY layer in
    line (its ``least`` out of reach): ``toks[:n]`` prefilled and the rest
    decoded through pages. A dict each: the logits a call, every store whole,
    the statistics the prefill's layers reported (the assignment shares and
    ``hc_sinkhorn_error``) and ``ray_tpu_serve_engine_traced_layers``."""
    def gauge(name):
        return dict(registry().local_values(name))

    def serve():
        engine = make_engine()
        ps = engine.page_size
        pages = engine.pool.alloc(-(-len(toks) // ps))
        traced = gauge("ray_tpu_serve_engine_traced_layers")[()]
        logits = [engine.prefill([int(t) for t in toks[:n]],
                                 pages[:-(-n // ps)])]
        stats = {name: gauge(name) for name in (
            "ray_tpu_serve_moe_assignment_share",
            "ray_tpu_serve_hc_sinkhorn_error")}
        for j in range(n, len(toks)):
            logits.append(engine.decode(j, int(toks[j]),
                                        pages[:j // ps + 1]))
        return {"logits": np.stack(logits), "traced": traced, "stats": stats,
                "stores": [np.asarray(a) for a in engine.stores]}

    with monkeypatch.context() as patch:
        patch.setattr(llama, "_segments",
                      partial(llama._segments, least=10 ** 6))
        in_line = serve()
    return serve(), in_line


def assert_served_alike(got, want, tol=1e-5):
    """Two of :func:`walked_both_ways`' dicts: logits, every store's rows
    and the layers' statistics within ``tol`` of the largest value."""
    def alike(a, b, what):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert a.shape == b.shape, what
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), what

    alike(got["logits"], want["logits"], "logits")
    assert len(got["stores"]) == len(want["stores"])
    for i, (a, b) in enumerate(zip(got["stores"], want["stores"])):
        assert np.abs(b).max() > 0, f"store {i} holds nothing"
        alike(a, b, f"store {i}")
    assert got["stats"].keys() == want["stats"].keys()
    for name, values in want["stats"].items():
        assert values.keys() == got["stats"][name].keys(), name
        for tags, value in values.items():
            alike(got["stats"][name][tags], value, (name, tags))


def traced_prefill_step(cfg, kind, monkeypatch, positions=256):
    """``prefill_attend_paths()``'s record of ``kind`` after a prefill of
    ``positions`` tokens is TRACED (shapes alone, nothing lowered) with the
    selection's kernels steered on, as a TPU backend takes them; in a
    record of the test's own, so that no other test reads a steered path."""
    monkeypatch.setattr(llama, "selected_attend_path",
                        lambda q, cd: ("kernel", "steered by a test"))
    monkeypatch.setattr(llama, "_prefill_attend_taken", {})
    params = jax.eval_shape(lambda key: llama.serving_params(
        cfg, llama.init_params(cfg, key)), jax.random.PRNGKey(0))
    stores = [jax.ShapeDtypeStruct(s.shape(2, 0, positions), jnp.float32)
              for s in llama.served_stores(cfg)]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    jax.eval_shape(partial(llama.prefill_with_cache, cfg), params, *stores,
                   i32(1, positions), i32(1), i32())
    mine, = [r for r in llama.prefill_attend_paths() if r["kind"] == kind]
    return mine
