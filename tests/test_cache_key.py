"""What of a source location reaches a compile-cache key
(``ray_tpu/util/compile_cache.py``): a Mosaic kernel's serialized body is its
custom call's ``backend_config``, which jax hashes as it stands. Lowered for
a v5e that is described and not attached; nothing is compiled and nothing
runs, so nothing here is a time."""

import hashlib
import importlib.util
import os
import re
import shutil
import sys

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ray_tpu.util import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL = os.path.join("ray_tpu", "ops", "flash_prefill.py")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _kernel_bodies(one_chip, call):
    """sha256 of every Mosaic call's ``backend_config`` in ``call`` lowered
    at a small shape (2 heads on 1, 256 positions)."""
    def arg(heads):
        return jax.ShapeDtypeStruct((1, 256, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(call).lower(arg(2), arg(1), arg(1)).as_text()
    bodies = re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"', text)
    assert bodies and "tpu_custom_call" in text
    return [hashlib.sha256(b.encode()).hexdigest() for b in bodies]


def test_a_kernels_key_holds_nothing_of_its_callers_lines(one_chip, tmp_path):
    """Two callers that differ in the line of the call (and in a frame above
    it): the same kernel body. At jax's defaults the body holds the stack."""
    compile_cache.configure()
    bodies = []
    for blank, name in ((0, "caller_a"), (7, "caller_b")):
        path = tmp_path / f"{name}.py"
        path.write_text(
            "\n" * blank +
            "from ray_tpu.ops.flash_prefill import flash_prefill\n\n\n"
            "def attend(q, k, v):\n"
            "    return flash_prefill(q, k, v)\n\n\n"
            "def call(q, k, v):\n" + "\n" * blank +
            "    return attend(q, k, v)\n")
        bodies.append(_kernel_bodies(one_chip, _load(name, str(path)).call))
    assert bodies[0] == bodies[1]


def test_a_kernels_key_holds_nothing_of_the_checkouts_path(one_chip,
                                                           tmp_path):
    """The package under two directories, each configured by ITS copy of the
    rule: one body. (Two copies and not this checkout against one: the
    setting is part of jax's trace context, so each copy's kernel is traced
    afresh, whatever this process traced before; see PERF.md section 7 for
    what a jax helper traced earlier leaves in a body.)"""
    compile_cache.configure()
    rule = os.path.join("ray_tpu", "util", "compile_cache.py")
    setting = "jax_hlo_source_file_canonicalization_regex"
    bodies, regexes = [], []
    try:
        for name in ("one", "another"):
            for rel in (KERNEL, rule):
                os.makedirs(tmp_path / name / os.path.dirname(rel))
                shutil.copy(os.path.join(REPO, rel), tmp_path / name / rel)
            its_rule = _load(f"compile_cache_{name}",
                             str(tmp_path / name / rule))
            regexes.append(its_rule.PATH_REGEX)
            jax.config.update(setting, its_rule.PATH_REGEX)
            kernel = _load(f"flash_prefill_{name}",
                           str(tmp_path / name / KERNEL))
            bodies.append(_kernel_bodies(
                one_chip, lambda q, k, v: kernel.flash_prefill(q, k, v)))
    finally:
        jax.config.update(setting, compile_cache.PATH_REGEX)
    assert len({compile_cache.PATH_REGEX, *regexes}) == 3
    assert bodies[0] == bodies[1]


def test_configure_reaches_a_childs_environment_and_a_loaded_jax(monkeypatch):
    """The two routes of ``configure``: the environment a child inherits
    (a worker's included), and the config of a process that imported jax
    before it."""
    env = {}
    compile_cache.configure(env)
    assert env["JAX_TRACEBACK_IN_LOCATIONS_LIMIT"] == "1"
    assert env["JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX"] \
        == "^" + re.escape(REPO + os.sep)
    from ray_tpu.core import accelerators

    worker = accelerators.worker_env({}, (0,), 1)
    assert {k: worker[k] for k in env} == env
    # a process that imported jax first and holds jax's defaults
    assert sys.modules["jax"] is jax
    was = {name: getattr(jax.config, name) for name in compile_cache.LOCATIONS}
    try:
        jax.config.update("jax_traceback_in_locations_limit", 10)
        jax.config.update("jax_hlo_source_file_canonicalization_regex", None)
        for name in compile_cache.LOCATIONS:
            monkeypatch.delenv(name.upper(), raising=False)
        compile_cache.configure()
        assert {name: getattr(jax.config, name)
                for name in compile_cache.LOCATIONS} == compile_cache.LOCATIONS
        assert all(os.environ[name.upper()] == env[name.upper()]
                   for name in compile_cache.LOCATIONS)
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
