"""The state-space layers' prefill kernel (``ops/ssm_prefill.py``),
interpreted on the CPU, against the XLA form it replaces on a TPU (``ops/ssm.py
_piece`` / ``scan_positions``: the causal convolution, ``silu``, the split,
``softplus`` and the chunked scan); the faults the cell's check was calibrated
on, each against the sound oracle; the rule that chooses between the two
paths, its record, what ``jax.grad`` gets, and who imports the module."""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import ssm
from ray_tpu.ops import ssm_prefill as sp
from test_flash_prefill import attend_gauge

N, TAPS = 128, 4


def config(dtype=jnp.float32, **fields):
    """What ``attend_ssm`` and the rule read of a config."""
    return types.SimpleNamespace(**{**dict(
        dtype=dtype, ssm_heads=8, ssm_head_dim=64, ssm_groups=1,
        ssm_state=N, ssm_chunk=128), **fields})


def operands(seed, B, T, dtype, H=8, P=64, start=False):
    """``xBC`` and ``dt`` as an in-projection leaves them, one layer's
    weights with decays of every size (``A`` from 0.25 to 16, steps around
    0.1) and every leaf off its neutral value, and where the sequence
    stands: zeros, or with ``start`` a state and a tail as a decode call
    would find them."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 9))
    width = H * P + 2 * N
    xbc = jax.random.normal(next(ks), (B, T, width)).astype(dtype)
    dt = jax.random.normal(next(ks), (B, T, H)) - 2.0
    p = {"conv_w": jax.random.normal(next(ks), (TAPS, width)) * 0.5,
         "conv_b": jax.random.normal(next(ks), (width,)) * 0.3,
         "dt_bias": jax.random.normal(next(ks), (H,)) * 0.5,
         "A_log": jnp.log(jax.random.uniform(next(ks), (H,), minval=0.25,
                                             maxval=16.0)),
         "D": 1.0 + 0.3 * jax.random.normal(next(ks), (H,))}
    state = 0.5 * start * jax.random.normal(next(ks), (B, H, P, N))
    tail = (start * jax.random.normal(next(ks), (B, TAPS - 1, width))
            ).astype(dtype)
    return xbc, dt, p, state, tail


def kernel(xbc, dt, p, state, tail, last=None, *, chunk, H=8, P=64):
    return sp.ssm_prefill(xbc, dt, p, state, tail, last, heads=H, head_dim=P,
                          chunk=chunk, interpret=True)


def oracle(xbc, dt, p, state, tail, last=None, *, chunk, H=8, P=64):
    """``ops/ssm.py``'s stretch from a state and a tail."""
    return ssm._piece(xbc, dt, p, heads=H, head_dim=P, groups=1, state=N,
                      chunk=chunk, start=state, before=tail, last=last)


def off(got, want) -> float:
    return float(jnp.linalg.norm((got.astype(jnp.float32) - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


# (positions, sequences, type, chunk, heads, their width, from a kept state
# and tail, last): one row tile; several; the last real position nowhere,
# inside a chunk, a chunk's last row, the first position; heads 64 and 128
# wide; eight pages' worth of chunks (64) at eight heads
CASES = {
    "one tile": (128, 1, jnp.float32, 128, 8, 64, False, None),
    "one tile of 256": (256, 1, jnp.float32, 256, 8, 64, False, None),
    "two sequences from a kept state": (512, 2, jnp.float32, 256, 8, 64,
                                        True, None),
    "three tiles, last inside the second": (384, 1, jnp.float32, 128, 8, 64,
                                            False, 200),
    "last a tile's last row, kept state": (384, 1, jnp.float32, 128, 8, 64,
                                           True, 255),
    "last the first position": (256, 1, jnp.float32, 128, 8, 64, True, 0),
    "bfloat16 from a kept state": (512, 1, jnp.bfloat16, 256, 8, 64, True,
                                   None),
    "bfloat16, two sequences, last inside": (768, 2, jnp.bfloat16, 256, 8,
                                             64, False, 600),
    "sixteen heads": (256, 1, jnp.float32, 128, 16, 64, True, 177),
    "heads of 128": (256, 1, jnp.float32, 128, 8, 128, True, 77),
    "heads of 128, bfloat16": (512, 1, jnp.bfloat16, 256, 8, 128, False,
                               None),
    "heads of 256, two blocks a head": (256, 1, jnp.float32, 128, 8, 256,
                                        True, 200),
    "64 chunks, bfloat16": (8192, 1, jnp.bfloat16, 128, 8, 64, False, 8000),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_chunked_form(case):
    """``y`` up to ``last`` and the state after ``last``: the XLA form's to
    rounding (it rounds the same operands at the same places), with ``last``
    traced as the engine traces it."""
    T, B, dtype, chunk, H, P, start, last = CASES[case]
    args = operands(T + B, B, T, dtype, H, P, start)
    at = None if last is None else jnp.int32(last)
    dims = dict(chunk=chunk, H=H, P=P)
    got_y, got_s = jax.jit(lambda *a: kernel(*a, **dims))(*args, at)
    assert got_y.shape == (B, T, H, P) and got_y.dtype == jnp.float32
    assert got_s.shape == (B, H, P, N) and got_s.dtype == jnp.float32
    want_y, want_s = jax.jit(lambda *a: oracle(*a, **dims))(*args, at)
    live = T if last is None else last + 1
    limit = 2e-5 if dtype == jnp.float32 else 2e-3
    assert off(got_y[:, :live], want_y[:, :live]) < limit
    assert off(got_s, want_s) < limit


def test_two_calls_in_sequence_are_one_call():
    """The state and the tail a call leaves are what the next one starts
    from: as chunked prefill would use it."""
    xbc, dt, p, state, tail = operands(3, 1, 512, jnp.float32, start=True)
    whole_y, whole_s = kernel(xbc, dt, p, state, tail, chunk=128)
    y1, s1 = kernel(xbc[:, :256], dt[:, :256], p, state, tail, chunk=128)
    y2, s2 = kernel(xbc[:, 256:], dt[:, 256:], p, s1,
                    ssm.conv_tail(xbc[:, :256], TAPS), chunk=128)
    assert off(jnp.concatenate([y1, y2], axis=1), whole_y) < 2e-6
    assert off(s2, whole_s) < 2e-6


# the faults the cell's check was calibrated on (PERF.md, PR 58), each as
# the ORACLE computed that wrong way: the kernel is far from every one of
# them, and as near the sound one as test_the_kernel_is_the_chunked_form says
FAULTS = {
    "the bias dropped": lambda a: dict(a, p=dict(
        a["p"], conv_b=jnp.zeros_like(a["p"]["conv_b"]))),
    "D x dropped": lambda a: dict(a, p=dict(
        a["p"], D=jnp.zeros_like(a["p"]["D"]))),
    "pads left live": lambda a: dict(a, last=None),
    "the start state ignored": lambda a: dict(
        a, state=jnp.zeros_like(a["state"])),
    "the tail ignored": lambda a: dict(a, tail=jnp.zeros_like(a["tail"])),
    "dt's bias dropped": lambda a: dict(a, p=dict(
        a["p"], dt_bias=jnp.zeros_like(a["p"]["dt_bias"]))),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_named_fault_shows(fault):
    xbc, dt, p, state, tail = operands(21, 1, 256, jnp.float32, start=True)
    sound = dict(xbc=xbc, dt=dt, p=p, state=state, tail=tail,
                 last=jnp.int32(140))
    got_y, got_s = kernel(**sound, chunk=128)
    wrong_y, wrong_s = oracle(**FAULTS[fault](sound), chunk=128)
    # what a decode call reads of it: the state after ``last`` and, for what
    # leaves no trace in the state, the live rows of ``y``
    assert max(off(got_s, wrong_s), off(got_y[:, :141], wrong_y[:, :141])) \
        > 1e-2


def test_row_tiles_and_what_the_call_refuses():
    assert sp.pick_rows(4096, 256) == 256 and sp.pick_rows(16384, 256) == 256
    assert sp.pick_rows(384, 128) == 128
    assert sp.pick_rows(384, 256) is None and sp.pick_rows(512, 64) is None
    assert sp.pick_rows(1024, 512) is None
    xbc, dt, p, state, tail = operands(0, 1, 128, jnp.float32)
    with pytest.raises(ValueError, match="xbc"):
        kernel(xbc[:, :100], dt[:, :100], p, state, tail, chunk=128)
    with pytest.raises(ValueError, match="heads"):
        kernel(xbc, dt, p, state, tail, chunk=128, H=4, P=128)
    with pytest.raises(ValueError, match="state"):
        kernel(xbc, dt, p, state[..., :64], tail, chunk=128)


# --- the rule: which path, why, and what it leaves to jax.grad ------------- #

REFUSED = {
    "backend": (dict(), dict(), "cpu", "backend is 'cpu', not tpu"),
    "rows in another type": (dict(), dict(xbc=jnp.bfloat16), "tpu",
                             "bfloat16"),
    "dt in the compute type": (dict(dtype=jnp.bfloat16),
                               dict(xbc=jnp.bfloat16, dt=jnp.bfloat16),
                               "tpu", "and float32"),
    "two groups": (dict(ssm_groups=2), dict(), "tpu", "2 groups"),
    "a state of 64": (dict(ssm_state=64), dict(), "tpu", "state width 64"),
    "heads of 32": (dict(ssm_head_dim=32), dict(), "tpu", "heads of 32"),
    "heads of 192": (dict(ssm_head_dim=192), dict(), "tpu", "heads of 192"),
    "twelve heads": (dict(ssm_heads=12), dict(), "tpu", "12 heads"),
    "100 positions": (dict(), dict(positions=100), "tpu", "100 positions"),
    "chunks of 64": (dict(ssm_chunk=64), dict(), "tpu", "chunks of 64"),
    "chunks of 512": (dict(ssm_chunk=512), dict(positions=1024), "tpu",
                      "chunks of 512"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_the_rule_refuses_with_its_reason(what, monkeypatch):
    fields, shapes, backend, why = REFUSED[what]
    cfg = config(**fields)
    T = shapes.get("positions", 256)
    xbc = jnp.zeros((1, T, 8), shapes.get("xbc", cfg.dtype))
    dt = jnp.zeros((1, T, 8), shapes.get("dt", jnp.float32))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    path, reason = llama.ssm_prefill_path(cfg, xbc, dt)
    assert path == "chunks" and why in reason


def test_the_rule_takes_the_cells_widths_on_a_tpu(monkeypatch):
    """Every page count the cell runs, the 2 pages of the harness's check
    included; and a second model's widths (heads of 128)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = config(jnp.bfloat16, ssm_heads=64, ssm_chunk=256)
    for pages in range(1, 9):
        xbc = jax.ShapeDtypeStruct((1, 2048 * pages, 4352), jnp.bfloat16)
        dt = jax.ShapeDtypeStruct((1, 2048 * pages, 64), jnp.float32)
        assert llama.ssm_prefill_path(cfg, xbc, dt) == ("kernel",
                                                        "tpu backend")
    cfg = config(jnp.bfloat16, ssm_heads=32, ssm_head_dim=128)
    assert llama.ssm_prefill_path(
        cfg, jax.ShapeDtypeStruct((2, 1024, 4352), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 1024, 32), jnp.float32))[0] == "kernel"


def on_the_kernel_path(monkeypatch):
    """Steer this CPU process onto the kernel's path (it is interpreted
    there), as ``test_gdn_prefill.py`` steers ``attend_delta``."""
    monkeypatch.setattr(llama, "ssm_prefill_path",
                        lambda *a: ("kernel", "steered by a test"))


# the last real position: none (the last); inside the first tile of 128;
# inside a later tile; a tile's last row; the first position
@pytest.mark.parametrize("last", [None, 37, 200, 255, 0])
@pytest.mark.parametrize("sequences", [1, 2])
def test_attend_ssm_on_the_kernels_path_stops_at_last(last, sequences,
                                                      monkeypatch):
    """What the engine's prefill takes of it: the outputs up to ``last``,
    the state after ``last`` whatever follows, the tail at ``last``; with
    ``last`` traced, as the engine traces it."""
    T, cfg = 384, config()
    xbc, dt, p, _, _ = operands(11, sequences, T, jnp.float32)
    at = None if last is None else jnp.int32(last)
    fn = jax.jit(lambda last, *a: llama.attend_ssm(cfg, last, *a))
    want = fn(at, xbc, dt, p)
    live = T if last is None else last + 1
    whole = 128 * -(-live // 128)
    # the prompt cut there, what is behind ``last`` switched off by hand (a
    # step of softplus(-1e9) = 0 is the identity update)
    cut = fn(None, xbc[:, :whole], jnp.where(
        (jnp.arange(T) < live)[None, :, None], dt, -1e9)[:, :whole], p)
    on_the_kernel_path(monkeypatch)
    got = jax.jit(lambda last, *a: llama.attend_ssm(cfg, last, *a))(
        at, xbc, dt, p)
    assert off(got[0][:, :live], want[0][:, :live]) < 2e-5
    assert got[1].shape == (sequences, 1, 8, 64, N)
    assert off(got[1], want[1]) < 2e-5
    np.testing.assert_array_equal(got[2], want[2])
    assert off(got[1], cut[1]) < 2e-5  # the state of the prompt cut there


def test_a_traced_layer_is_counted_with_its_reason(monkeypatch):
    """Where a program is traced: the gauge's two series of kind ``ssm``
    (both set, the one not taken at what it has counted), the record with
    its reason."""
    cfg = config()
    xbc, dt, p, _, _ = operands(2, 1, 128, jnp.float32)
    before = attend_gauge()
    jax.jit(lambda *a: llama.attend_ssm(cfg, None, *a))(xbc, dt, p)
    after = attend_gauge()
    assert after[("ssm", "chunks")] == before.get(("ssm", "chunks"), 0) + 1
    assert after[("ssm", "kernel")] == before.get(("ssm", "kernel"), 0)
    assert ("ssm", "tiles") not in after
    on_the_kernel_path(monkeypatch)
    jax.jit(lambda *a: llama.attend_ssm(cfg, None, *a))(xbc, dt, p)
    assert attend_gauge()[("ssm", "kernel")] == after[("ssm", "kernel")] + 1
    assert attend_gauge()[("ssm", "chunks")] == after[("ssm", "chunks")]
    mine = {r["path"]: r for r in llama.prefill_attend_paths()
            if r["kind"] == "ssm" and r["q_shape"] == [1, 128, 8 * 64 + 2 * N]}
    assert "'cpu'" in mine["chunks"]["reason"]
    assert mine["kernel"]["reason"] == "steered by a test"


def test_grad_through_the_kernels_path_is_the_chunked_forms(monkeypatch):
    """The value is the kernel's; ``jax.grad`` runs the XLA path's transpose
    from the operands, and both are what that path gives."""
    cfg = config()
    xbc, dt, p, _, _ = operands(5, 1, 128, jnp.float32)

    def loss(xbc, dt, p):
        y, state, tail = llama.attend_ssm(cfg, jnp.int32(100), xbc, dt, p)
        return jnp.sum(jnp.sin(y[:, :101])) + jnp.sum(state * state) \
            + jnp.sum(tail)

    n = (0, 1, 2)
    want = jax.jit(jax.value_and_grad(loss, argnums=n))(xbc, dt, p)
    on_the_kernel_path(monkeypatch)
    got = jax.jit(jax.value_and_grad(loss, argnums=n))(xbc, dt, p)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for mine, theirs in zip(jax.tree.leaves(got[1]),
                            jax.tree.leaves(want[1])):
        np.testing.assert_allclose(mine, theirs, atol=5e-4, rtol=5e-4)


# --- who imports it ------------------------------------------------------- #


def test_a_train_process_never_imports_the_kernel():
    """The module is reachable from the served ``H`` row alone, imported
    where it is called: after what a train cell imports (and the mixer's own
    module), ``ray_tpu.ops.ssm_prefill`` is not among the loaded modules,
    and neither is ``ops/gdn_prefill.py``."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import ray_tpu, ray_tpu.train, ray_tpu.models.llama, "
         "ray_tpu.ops.ssm\n"
         "print(sorted(m for m in sys.modules if m.endswith('_prefill')))"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
