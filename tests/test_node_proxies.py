"""Per-node Serve proxies (ProxyLocation.EveryNode analog)."""

import json
import urllib.request

import pytest

import ray_tpu


def test_every_node_proxies(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)  # second (in-process) node
    from ray_tpu import serve

    serve.start(http_options=serve.HTTPOptions(
        host="127.0.0.1", port=0, proxy_location="EveryNode"))
    # serve.run() below starts the driver's own proxy where there is none,
    # on the default port 8000, which another xdist worker's serve test
    # may hold: give it a port of its own first
    serve.start(http_options=serve.HTTPOptions(host="127.0.0.1", port=0))

    @serve.deployment(num_replicas=1)
    class Hello:
        def __call__(self, req):
            return {"hi": req.query_params.get("name", "world")}

    serve.run(Hello.bind(), route_prefix="/hello")
    addrs = serve.get_proxy_addresses()
    # one proxy per node, keyed by real node id
    assert len(addrs) == 2, addrs
    node_ids = {a["node_id"] for a in addrs}
    cluster_ids = {n["NodeID"] for n in ray_tpu.nodes() if n["Alive"]}
    assert node_ids == cluster_ids
    for a in addrs:
        host = a["host"] if a["host"] != "0.0.0.0" else "127.0.0.1"
        with urllib.request.urlopen(
                f"http://{host}:{a['port']}/hello?name=x", timeout=30) as r:
            assert json.loads(r.read().decode()) == {"hi": "x"}

    # reconciliation: a node added AFTER start gets a proxy
    cluster.add_node(num_cpus=1)
    from ray_tpu.serve import api as serve_api

    serve_api._proxy_manager.reconcile()
    addrs2 = serve.get_proxy_addresses()
    assert len(addrs2) == 3, addrs2
    serve.shutdown()
