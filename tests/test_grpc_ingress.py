"""Serve gRPC ingress: generic bytes-in/bytes-out routing to deployments."""

import json

import pytest

import ray_tpu

grpc = pytest.importorskip("grpc")


@pytest.fixture
def grpc_serve(ray_start_regular):
    from ray_tpu import serve

    # the HTTP proxy starts too: on a port of its own, not the default
    # 8000 that another xdist worker's serve test may hold
    serve.start(serve.HTTPOptions(port=0),
                grpc_options=serve.gRPCOptions(port=0))
    yield serve
    serve.shutdown()


class TestGRPCIngress:
    def test_unary_roundtrip_and_errors(self, grpc_serve):
        serve = grpc_serve

        @serve.deployment
        class Echo:
            def __call__(self, req):
                return b"echo:" + req.body()

            def stats(self, req):
                return {"n": len(req.body())}

        serve.run(Echo.bind(), route_prefix="/echo")
        port = serve.get_grpc_ingress().port
        ch = grpc.insecure_channel(f"127.0.0.1:{port}")

        call = ch.unary_unary("/ray_tpu.serve/Echo")
        assert call(b"hi", timeout=60) == b"echo:hi"

        # method addressing: <deployment>.<method>
        call2 = ch.unary_unary("/ray_tpu.serve/Echo.stats")
        assert json.loads(call2(b"abcd", timeout=60)) == {"n": 4}

        # unknown deployment -> NOT_FOUND
        bad = ch.unary_unary("/ray_tpu.serve/Nope")
        with pytest.raises(grpc.RpcError) as e:
            bad(b"x", timeout=30)
        assert e.value.code() == grpc.StatusCode.NOT_FOUND

        # deployment exception -> INTERNAL
        @serve.deployment
        class Boom:
            def __call__(self, req):
                raise ValueError("nope")

        serve.run(Boom.bind(), route_prefix="/boom")
        boom = ch.unary_unary("/ray_tpu.serve/Boom")
        with pytest.raises(grpc.RpcError) as e:
            boom(b"x", timeout=60)
        assert e.value.code() == grpc.StatusCode.INTERNAL
        ch.close()

    def test_multiplexed_metadata(self, grpc_serve):
        serve = grpc_serve

        @serve.deployment
        class Multi:
            @serve.multiplexed(max_num_models_per_replica=2)
            async def get_model(self, mid):
                return "M" + mid

            async def __call__(self, req):
                return await self.get_model(
                    serve.get_multiplexed_model_id())

        serve.run(Multi.bind(), route_prefix="/multi")
        port = serve.get_grpc_ingress().port
        ch = grpc.insecure_channel(f"127.0.0.1:{port}")
        call = ch.unary_unary("/ray_tpu.serve/Multi")
        out = call(b"", timeout=60,
                   metadata=(("multiplexed-model-id", "zz"),))
        assert out == b"Mzz"
        ch.close()
