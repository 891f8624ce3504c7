"""Cluster launcher: YAML -> head + autoscaler + dashboard; up/down from
separate processes (reference: `ray up/down cluster.yaml`)."""

import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_yaml(tmp_path, name, min_workers=1, max_workers=2):
    cfg = textwrap.dedent(f"""
        cluster_name: {name}
        min_workers: {min_workers}
        max_workers: {max_workers}
        idle_timeout_s: 60
        provider:
          type: local
        head:
          num_cpus: 1
          num_tpus: 0
          dashboard_port: 0
        worker_nodes:
          num_cpus: 2
          num_tpus: 0
    """)
    path = tmp_path / "cluster.yaml"
    path.write_text(cfg)
    return str(path)


def test_config_validation(tmp_path):
    from ray_tpu.cluster_launcher import load_cluster_config

    p = tmp_path / "bad.yaml"
    p.write_text("min_workers: 1\n")
    with pytest.raises(ValueError, match="cluster_name"):
        load_cluster_config(str(p))
    cfg = load_cluster_config(_write_yaml(tmp_path, "ok"))
    assert cfg["cluster_name"] == "ok"
    assert cfg["worker_nodes"]["num_cpus"] == 2


def test_up_status_down_cross_process(tmp_path):
    """`up` in a child process; status + a remote driver + `down` from
    this one — the full operator flow."""
    from ray_tpu.cluster_launcher import read_cluster_state

    yaml_path = _write_yaml(tmp_path, "launchtest", min_workers=1)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu", "up", yaml_path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        # wait for the state file + min_workers node join
        deadline = time.time() + 120
        state = None
        while time.time() < deadline:
            state = read_cluster_state("launchtest")
            if state:
                break
            time.sleep(0.5)
        assert state, "cluster state file never appeared"
        deadline = time.time() + 120
        nodes = []
        while time.time() < deadline:
            # re-read the state each round: a STALE state file (left by
            # a previous run killed mid-suite) points at a dead
            # dashboard — the fresh `up` overwrites it with the live
            # address once its own init completes
            state = read_cluster_state("launchtest") or state
            host, port = state["dashboard"]
            try:
                with urllib.request.urlopen(
                        f"http://{host}:{port}/api/nodes", timeout=5) as r:
                    nodes = json.loads(r.read().decode())
                if len([n for n in nodes if n["alive"]]) >= 2:
                    break  # head + min_workers=1
            except Exception:
                pass
            time.sleep(0.5)
        assert len([n for n in nodes if n["alive"]]) >= 2, nodes
        host, port = state["dashboard"]

        # a remote driver connects through the launched cluster
        ch, cp = state["client_address"]
        code = ("import ray_tpu; ray_tpu.init(); "
                "f = ray_tpu.remote(lambda x: x * 7); "
                "print('UP', ray_tpu.get(f.remote(6))); "
                "ray_tpu.shutdown()")
        cenv = dict(env)
        cenv["RAY_TPU_ADDRESS"] = f"ray_tpu://{ch}:{cp}"
        cenv["RAY_TPU_CLUSTER_KEY"] = state["cluster_key"]
        out = subprocess.run([sys.executable, "-c", code], env=cenv,
                             capture_output=True, text=True, timeout=120)
        assert "UP 42" in out.stdout, (out.stdout, out.stderr)

        # down from a separate process
        rc = subprocess.run(
            [sys.executable, "-m", "ray_tpu", "down", yaml_path],
            env=env, capture_output=True, text=True, timeout=60)
        assert rc.returncode == 0, rc.stdout + rc.stderr
        deadline = time.time() + 30
        while time.time() < deadline and proc.poll() is None:
            time.sleep(0.3)
        assert proc.poll() is not None, "head process did not exit"
    finally:
        if proc.poll() is None:
            proc.kill()
