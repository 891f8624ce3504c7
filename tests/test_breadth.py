"""Round-2 breadth: Tune PB2 + callbacks/loggers, Serve multiplexing,
Data read_sql/from_torch."""

import json
import os
import sqlite3

import numpy as np
import pytest

import ray_tpu


class TestPB2:
    def test_gp_selection_within_bounds(self):
        from ray_tpu.tune.schedulers import PB2

        pb2 = PB2(metric="score", mode="max",
                  hyperparam_bounds={"lr": (1e-4, 1e-1)}, seed=0)

        class T:
            trial_id = "t1"
            config = {"lr": 1e-3}

        # feed observations so the GP path runs
        for i, s in enumerate([1.0, 2.0, 4.0, 7.0, 11.0, 16.0]):
            pb2._observe(T, i, s)
        new = pb2._mutate({"lr": 1e-3})
        assert 1e-4 <= new["lr"] <= 1e-1

    def test_pb2_under_tune(self, ray_start_regular, tmp_path):
        from ray_tpu import tune
        from ray_tpu.train import RunConfig
        from ray_tpu.tune.schedulers import PB2

        def trainable(config):
            for i in range(6):
                tune.report({"score": config["x"] * (i + 1)})

        tuner = tune.Tuner(
            trainable,
            param_space={"x": tune.uniform(0.0, 1.0)},
            tune_config=tune.TuneConfig(
                metric="score", mode="max", num_samples=4,
                scheduler=PB2(perturbation_interval=2,
                              hyperparam_bounds={"x": (0.0, 1.0)})),
            run_config=RunConfig(name="pb2", storage_path=str(tmp_path)),
        )
        results = tuner.fit()
        assert results.get_best_result("score", "max") is not None


class TestTuneCallbacks:
    def test_loggers_write_files(self, ray_start_regular, tmp_path):
        from ray_tpu import tune
        from ray_tpu.train import RunConfig
        from ray_tpu.tune import CSVLoggerCallback, JsonLoggerCallback

        events = []

        class Probe(tune.Callback):
            def on_trial_start(self, it, trials, trial):
                events.append("start")

            def on_trial_complete(self, it, trials, trial):
                events.append("complete")

            def on_experiment_end(self, trials):
                events.append("end")

        def trainable(config):
            for i in range(3):
                tune.report({"loss": 1.0 / (i + 1)})

        tuner = tune.Tuner(
            trainable, param_space={"x": tune.choice([1, 2])},
            tune_config=tune.TuneConfig(metric="loss", mode="min",
                                        num_samples=2),
            run_config=RunConfig(
                name="cb", storage_path=str(tmp_path),
                callbacks=[JsonLoggerCallback(), CSVLoggerCallback(),
                           Probe()]),
        )
        results = tuner.fit()
        assert events.count("start") >= 2
        assert events.count("complete") >= 2
        assert events[-1] == "end"
        trial_dirs = [t.trial_dir for t in results._trials]
        found_json = found_csv = 0
        for d in trial_dirs:
            jp, cp = os.path.join(d, "result.json"), os.path.join(
                d, "progress.csv")
            if os.path.exists(jp):
                found_json += 1
                lines = open(jp).read().strip().splitlines()
                assert len(lines) == 3
                assert "loss" in json.loads(lines[0])
            if os.path.exists(cp):
                found_csv += 1
                content = open(cp).read()
                assert "loss" in content.splitlines()[0]
        assert found_json == 2 and found_csv == 2


class TestServeMultiplex:
    def test_lru_and_sticky_routing(self, ray_start_regular):
        from ray_tpu import serve

        @serve.deployment(num_replicas=2)
        class Multi:
            def __init__(self):
                self.loads = []

            @serve.multiplexed(max_num_models_per_replica=2)
            async def get_model(self, model_id: str):
                self.loads.append(model_id)
                return f"model:{model_id}"

            async def __call__(self, req):
                mid = serve.get_multiplexed_model_id()
                model = await self.get_model(mid)
                return {"model": model, "loads": list(self.loads)}

        # not the default port 8000: another xdist worker may hold it
        serve.start(serve.HTTPOptions(port=0))
        handle = serve.run(Multi.bind(), route_prefix="/multi")
        h1 = handle.options(multiplexed_model_id="a")
        out1 = h1.remote({"x": 1}).result(timeout=60)
        assert out1["model"] == "model:a"
        # same model id -> same replica (sticky), and no re-load
        out2 = h1.remote({"x": 2}).result(timeout=60)
        assert out2["loads"].count("a") == 1
        # a third model on the same replica evicts LRU beyond capacity 2
        for mid in ("b", "c"):
            handle.options(multiplexed_model_id=mid).remote(
                {}).result(timeout=60)
        serve.shutdown()


class TestNewDatasources:
    def test_read_sql_sqlite(self, ray_start_regular, tmp_path):
        db = str(tmp_path / "t.db")
        conn = sqlite3.connect(db)
        conn.execute("CREATE TABLE kv (k INTEGER, v TEXT)")
        conn.executemany("INSERT INTO kv VALUES (?, ?)",
                         [(i, f"v{i}") for i in range(10)])
        conn.commit()
        conn.close()

        from ray_tpu import data

        ds = data.read_sql("SELECT k, v FROM kv ORDER BY k",
                           lambda: sqlite3.connect(db))
        rows = ds.take_all()
        assert len(rows) == 10
        assert rows[0]["v"] == "v0"

    @pytest.mark.slow  # >5s on the 1-core box: full-tier only (tier-1 wall budget)
    def test_from_torch(self, ray_start_regular):
        import torch
        from torch.utils.data import Dataset as TorchDataset

        class TD(TorchDataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return {"x": torch.tensor([i, i]), "y": i * 2}

        from ray_tpu import data

        ds = data.from_torch(TD(), parallelism=2)
        rows = ds.take_all()
        assert len(rows) == 8
        assert sorted(r["y"] for r in rows) == [0, 2, 4, 6, 8, 10, 12, 14]
        by_y = {r["y"]: r for r in rows}
        assert list(np.asarray(by_y[6]["x"])) == [3, 3]
