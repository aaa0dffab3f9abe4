"""Deployment modules: what a configuration's ``module`` gives the harness.

``deployments/emit_measurements.py`` serves every configuration the bytes
and the comparison the harness served before it was a module (chunk hashes
and the numbers of one fixed comparison, pinned from the commit before);
the new ``emit_measurements`` configuration is upstream's quick-start job on
``emit_sliding``'s feed; ``reference.verdict`` holds a module's new numbers
to the module's own limits and refuses a number no limit holds; and the
rideshare deployment of ``fixtures/entering/``, whose record, query and
reference are its own, runs through the real harness on the CPU, correct,
with a control that is not."""

import dataclasses
import hashlib
import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import events, lastline, manifest, reference, runner, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
EMIT = "benchmark/deployments/emit_measurements.py"
SEED = 3_700_000_101
XPLANE = os.path.join(FIXTURES, "small.xplane.pb")

#: sha256 of chunks 0-3 (each the blobs of its partitions, joined) of each
#: configuration's cell at ``SEED``, as ``feeder.encode_chunk`` made them
#: before the deployment module took the record over
PARENT_CHUNKS = {
    "emit_sliding": [
        "013987e8c21ddacccc70aff6bb2095e005be362a2efb697a6a51417d8b21b30e",
        "895d27535f58cc5f2f9c38879da41f49b0488c355d33f83f7fd103afccf1e78c",
        "e883f8f4cee801cfe3c2e43b985d45c3ca09f2872bd926026db42578830453d7",
        "7af4c24ecce88ebedbfba17dd2b795caf97b1552c239319ba347f4da012a0bf3"],
    "keyed_100k": [
        "90e65c739d52142f5348a4d9718cde6f1423dcd2d67a81429274beb630e58843",
        "ddc75d8487bfdbe56ccf8492dbd22a8635042b675686c6aa2b1bc39eb8889d40",
        "68219a3a29ef95ddb2326acdacac090cbb4dfe836cd8cf57ffb0e8e8e927b9d6",
        "57fd8cab45967399ca72fd85d1c1d6c21c9e46f1442775993aea1948e8573fc7"],
    "keyed_10m": [
        "2f0e6cbb75238e76482d1ba8a452acf1b5a620fcc79917e3a212c25357375a9f",
        "fd623d3f02fcbada8269dc914aba564e7a8391dfed6e69cfd6c756c913251797",
        "da91a60496fe0ccc4c5d4d9aa35cc68b170cebe785c8ba117e11374b02655ec9",
        "1832b4acc9201ec404d9f613ca4df3d8636916572ed04b2641a17de7b3d30408"],
    "keyed_40m": [
        "d74e30e3eb301bee94ae506c9d0a049622e48b3b56b17af6c7a71757e49aec0f",
        "3d74c0766eb71be94d8094806ac72fb962eaa1833e7d90d4b7b6b7921a995dbb",
        "36d616d6bb44e2c3d451b2835cf2bc13d141e1f72c627d8de1ad14cf2383e25d",
        "b3ebd4d9c4058efcb2dd27810dfc20e966b10c1481d733f48598f22885367a5f"],
}
PARENT_CHUNKS["sliding_10m"] = PARENT_CHUNKS["keyed_10m"]  # the same feed
#: upstream's quick-start job reads emit_sliding's feed, byte for byte
PARENT_CHUNKS["emit_measurements"] = PARENT_CHUNKS["emit_sliding"]

#: ``runner.check_blocks`` on ``fake_consumer(cell, SEED, 9)`` with the
#: control, as it read before the deployment module took the reference over
PARENT_CHECKS = {
    "tiny_sliding.drain": (
        {"rows_compared": 123, "missing_rows": 3, "unexpected_rows": 0,
         "count_mismatch": 0, "minmax_mismatch": 0,
         "rel_err_max": 1.0180753940944685e-06, "blocks_compared": 3},
        {"rows_compared": 126, "missing_rows": 0, "unexpected_rows": 0,
         "count_mismatch": 0, "minmax_mismatch": 0,
         "rel_err_max": 0.014383445913240638},
        3),
    "tiny_keyed.drain": (
        {"rows_compared": 2997, "missing_rows": 3, "unexpected_rows": 0,
         "count_mismatch": 0, "minmax_mismatch": 0,
         "rel_err_max": 1.0080271974607468e-06, "blocks_compared": 3},
        {"rows_compared": 3000, "missing_rows": 0, "unexpected_rows": 0,
         "count_mismatch": 0, "minmax_mismatch": 5924,
         "rel_err_max": 0.018168918499825906},
        3),
}


def tiny(name):
    return manifest.load_cell(
        name, os.path.join(FIXTURES, "BENCHMARK.json"),
        os.path.join(FIXTURES, "benchmark"))


def _bench():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- emit_measurements.py is the harness as it was ------------------------------


def test_every_configuration_names_the_emit_measurements_module():
    bench = _bench()
    for c in bench["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            assert json.load(f)["module"] == EMIT, c["name"]
    assert sorted(PARENT_CHUNKS) == sorted(c["name"] for c in bench["configs"])


@pytest.mark.parametrize("config", sorted(PARENT_CHUNKS))
def test_the_feed_is_byte_identical(config):
    cell = manifest.load_cell(next(
        w["name"] for w in _bench()["workloads"] if w["config"] == config))
    params = runner.feeder_params(cell, SEED)["feed"]
    got = [hashlib.sha256(b"".join(cell.deployment.encode_chunk(params, c)))
           .hexdigest() for c in range(4)]
    assert got == PARENT_CHUNKS[config]


def fake_consumer(cell, seed, n_blocks):
    """What a consumer keeps of an engine that left the first row of each
    sampled block out, nudged one value of the last column by 1e-6 and
    rounded the rest to float32: the windows of the first ``n_blocks``
    blocks arrived."""
    cfg, chk = cell.config, cell.traffic["check"]
    q = cfg["query"]
    feed = events.Feed(
        seed=seed, n_keys=cfg["keys"]["count"], key_prefix=cfg["keys"]["prefix"],
        partitions=cfg["partitions"], chunk_ms=cell.traffic["chunk_ms"],
        events_per_chunk=cell.traffic["events_per_second"]
        * cell.traffic["chunk_ms"] // 1000,
        records_per_batch=cfg["records_per_batch"])
    length, slide, block = q["length_ms"], q["slide_ms"], chk["block_ms"]
    aggs = [tuple(a) for a in q["aggregates"]]
    flt = (q["filter"]["column"], q["filter"]["gt"]) if q.get("filter") else None
    names = feed.key_names()
    c = types.SimpleNamespace(
        sampled=runner.sampled_blocks(seed, chk["every"]),
        columns=[q["keys"][0]] + [a[0] for a in aggs],
        arrival={events.T0 + k * slide + length: 0.0
                 for k in range(0, n_blocks * block // slide)},
        kept={})
    for b in np.flatnonzero(c.sampled[:n_blocks]).tolist():
        ref = reference.Reference(feed, length, slide, events.T0 + b * block,
                                  events.T0 + (b + 1) * block)
        fold = {k: v.astype(np.float32).astype(np.float64)
                for k, v in ref.fold().items()}
        rows = reference.rows_of(fold, ref, aggs, flt)
        cells = rows.pop("cells")
        keep = np.ones(len(cells), bool)
        keep[0] = False
        last = aggs[-1][0]
        rows[last] = rows[last].copy()
        rows[last][1] *= 1.000001
        ws = ref.window_starts()[cells // feed.n_keys][keep]
        cols = {c.columns[0]: names[cells % feed.n_keys][keep]}
        cols.update({k: v[keep] for k, v in rows.items()})
        c.kept[b] = [(ws, cols)]
    return c


@pytest.mark.parametrize("name", sorted(PARENT_CHECKS))
def test_the_comparison_reads_what_it_read(name):
    """A tumbling configuration and a sliding one with a filter."""
    cell = tiny(name)
    numbers, control, bad = runner.check_blocks(
        cell, SEED, fake_consumer(cell, SEED, 9), True)
    assert (numbers, control, bad) == PARENT_CHECKS[name]


def test_emit_measurements_is_the_quick_start_job_on_emit_slidings_feed():
    cell = manifest.load_cell("emit_measurements.drain")
    sliding = manifest.load_cell("emit_sliding.drain")
    assert cell.chips == 1 and set(cell.end_to_end) == {"events_per_s", "setup_s"}
    q = cell.config["query"]
    assert q == {"keys": ["sensor_name"], "aggregates": [
        ["count", "count"], ["min", "min"], ["max", "max"], ["average", "avg"]],
        "length_ms": 1000, "slide_ms": 1000, "filter": None}
    assert cell.config["engine"] == {"source_idle_timeout_ms": 1000}
    assert cell.config["reduced"] == []
    for key in ("topic", "partitions", "records_per_batch", "keys"):
        assert cell.config[key] == sliding.config[key], key
    assert {k: v for k, v in cell.traffic.items() if k != "config"} == {
        k: v for k, v in sliding.traffic.items() if k != "config"}
    assert set(cell.per_layer) == set(sliding.per_layer)


def test_emit_measurements_twin_is_correct_and_its_control_is_not(tmp_path):
    """The cell's own files at a tenth of the density, every other block
    sampled: through the real engine on the CPU."""
    os.makedirs(tmp_path / "benchmark" / "configs")
    os.makedirs(tmp_path / "benchmark" / "workloads")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(_bench()))
    real = manifest.load_cell("emit_measurements.drain")
    tr = dict(real.traffic, events_per_second=100_000, lead_events=200_000,
              warmup_s=0.3, warmup_timeout_s=120.0, encoders=1,
              check=dict(real.traffic["check"], every=2))
    (tmp_path / "benchmark" / "configs" / "emit_measurements.json").write_text(
        json.dumps(real.config))
    (tmp_path / "benchmark" / "workloads" / "emit_measurements.drain.json"
     ).write_text(json.dumps(tr))
    twin = manifest.load_cell("emit_measurements.drain",
                              str(tmp_path / "BENCHMARK.json"),
                              str(tmp_path / "benchmark"))
    logged = []
    text = runner.run_cell(twin, SEED, 2.0, False, require_tpu=False,
                           control=True, log=logged.append)
    line = json.loads(text)
    assert line["correct"] is True and line["failed"] == 0
    c = line["compared"]
    assert c["minmax_mismatch"]["value"] == c["count_mismatch"]["value"] == 0
    assert 0 < c["rel_err_max"]["value"] <= c["rel_err_max"]["limit"]
    run = json.loads(next(m for m in logged if m.startswith('{"workload"')))
    assert run["rows_compared"] >= 20 and run["blocks_compared"] >= 1
    assert "correct=False" in next(m for m in logged if m.startswith("control"))


# -- a module's own limits ------------------------------------------------------


def test_a_module_limit_holds_a_new_number_and_no_other():
    base = {"rows_compared": 10, "missing_rows": 0, "rel_err_max": 1e-7}
    ok, compared = reference.verdict({**base, "max_speed_mismatch": 0},
                                     {"max_speed_mismatch": 0})
    assert ok and compared["max_speed_mismatch"] == {"value": 0, "limit": 0}
    assert list(compared) == ["missing_rows", "rel_err_max", "max_speed_mismatch"]
    ok, _ = reference.verdict({**base, "max_speed_mismatch": 2},
                              {"max_speed_mismatch": 0})
    assert not ok
    # a module cannot loosen a limit the benchmark has
    ok, compared = reference.verdict({**base, "rel_err_max": 1e-3},
                                     {"rel_err_max": 1.0})
    assert not ok and compared["rel_err_max"]["limit"] == reference.LIMITS["rel_err_max"]


def test_a_number_no_limit_holds_is_refused():
    with pytest.raises(reference.Unlimited, match="max_speed_mismatch"):
        reference.verdict({"rows_compared": 10, "max_speed_mismatch": 0})
    with pytest.raises(reference.Unlimited, match="speed"):
        reference.verdict({"rows_compared": 10, "speed": 0}, {"max_speed": 0})


def _with_number(cell, value, limits):
    """The cell with a deployment whose comparison reports one more number."""
    real = cell.deployment

    def check(*args):
        numbers, control = real.check(*args)
        return {**numbers, "odd_rows": value}, control

    module = types.SimpleNamespace(**{**vars(real), "check": check})
    if limits is not None:
        module.LIMITS = limits
    return dataclasses.replace(cell, deployment=module)


def test_a_run_whose_module_reports_an_unlimited_number_has_no_result():
    cell = _with_number(tiny("tiny_keyed.drain"), 0, None)
    with pytest.raises(runner.RunFailed, match="odd_rows"):
        runner.run_cell(cell, SEED, 0.6, False, require_tpu=False,
                        log=lambda msg: None)


def test_a_run_is_held_to_its_module_limit():
    cell = _with_number(tiny("tiny_keyed.drain"), 1, {"odd_rows": 0})
    line = json.loads(runner.run_cell(cell, SEED, 0.6, False,
                                      require_tpu=False, log=lambda msg: None))
    assert line["correct"] is False
    assert list(line["compared"])[-1] == "odd_rows"
    # one a block, added up over the blocks compared
    assert line["compared"]["odd_rows"]["limit"] == 0
    assert line["compared"]["odd_rows"]["value"] >= 1


# -- a deployment with a record and a query of its own --------------------------


def test_the_entering_deployment_lies_under_the_fixtures_alone(entered_tree):
    assert not any("rideshare" in f for _r, _d, files in os.walk(
        manifest.BENCH_DIR) for f in files)
    assert "rideshare" not in json.dumps(_bench())
    cell = entered_tree.load_cell("rideshare.drain")
    assert cell.deployment.__file__ == os.path.join(
        entered_tree.root, "benchmark", "deployments", "rideshare.py")
    assert cell.deployment.kept_columns(cell.config)[0] == "driver_id"
    assert "emit_rows_per_row_in.rideshare" in cell.per_layer


@pytest.fixture(scope="module")
def rideshare(entered_tree):
    return entered_tree.load_cell("rideshare.drain")


def test_rideshare_runs_correct_and_its_control_does_not(rideshare):
    logged = []
    text = runner.run_cell(rideshare, 3_900_000_039, 2.0, False,
                           require_tpu=False, control=True, log=logged.append)
    assert lastline.check_text(text, rideshare.end_to_end, False) == []
    line = json.loads(text)
    assert line["correct"] is True and line["failed"] == 0
    c = line["compared"]
    # the module's own number, held to the module's own limit
    assert c["max_speed_mismatch"] == {"value": 0, "limit": 0}
    assert c["decode_fallback_rows"]["value"] == 0  # the nested path decoded
    assert 0 < c["rel_err_max"]["value"] <= c["rel_err_max"]["limit"]
    run = json.loads(next(m for m in logged if m.startswith('{"workload"')))
    assert run["rows_compared"] > 0 and run["blocks_compared"] >= 1
    control = next(m for m in logged if m.startswith("control"))
    assert "correct=False" in control


def test_rideshare_traced_reads_its_own_metric(rideshare, monkeypatch):
    monkeypatch.setattr(runner, "start_trace", lambda d: None)
    monkeypatch.setattr(runner, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: XPLANE)
    text = runner.run_cell(rideshare, 3_900_000_040, 1.0, True,
                           require_tpu=False, log=lambda msg: None)
    assert lastline.check_text(text, rideshare.per_layer, True,
                               rideshare.from_trace) == []
    line = json.loads(text)
    assert line["correct"] is True
    # a row a driver a slide out, of 2,000 rows an event-second in
    assert 0 < line["metrics"]["emit_rows_per_row_in.rideshare"]["value"] < 0.05
