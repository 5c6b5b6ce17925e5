"""A configuration, a traffic mix, a job, a metric and a cell's limits
dropped into a directory are found by name, with no edit of the harness."""
import json

from bench import run, traffic

JOB = '''
class Job:
    def __init__(self, *a, **k):
        self.kind = "toy"
'''
READER = '''
def read(view):
    return 42.0
'''


def test_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "jobs").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "limits").mkdir()
    (tmp_path / "configs" / "toy-model.json").write_text(json.dumps({"name": "toy-model"}))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps({"job": "toyjob", "block": 4}))
    (tmp_path / "jobs" / "toyjob.py").write_text(JOB)
    (tmp_path / "metrics" / "toy_share.x.py").write_text(READER)
    (tmp_path / "limits" / "toy-model.burst.json").write_text(
        json.dumps({"limits": {"n": 1.0}, "rehearse": {"n": 2.0}}))
    spec = {
        "configs": [{"name": "toy-model", "file": "configs/toy-model.json"}],
        "workloads": [{"name": "toy-model.burst", "config": "toy-model", "traffic": "burst",
                       "chips": 1}],
        "end_to_end": [{"name": "rate", "unit": "1/s"}, {"name": "setup_s", "unit": "s"},
                       {"name": "other", "unit": "s", "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "toy_share.x", "unit": "%", "moves": "rate"},
                      {"name": "not_here", "unit": "%", "moves": "rate", "workloads": ["x"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    got, cell, conf = run.load_cell("toy-model.burst", root=tmp_path)
    assert conf["file"] == "configs/toy-model.json" and cell["traffic"] == "burst"
    mix = traffic.load_mix("burst", root=tmp_path / "traffic")
    assert mix["job"] == "toyjob" and mix["name"] == "burst"
    assert run.load_job(mix["job"], root=tmp_path)().kind == "toy"
    assert run.load_reader("toy_share.x", root=tmp_path)(None) == 42.0
    assert run.load_limits("toy-model.burst", False, root=tmp_path) == {"n": 1.0}
    assert run.load_limits("toy-model.burst", True, root=tmp_path) == {"n": 2.0}
    assert [m["name"] for m in run.metric_entries(got, cell, False)] == ["rate", "setup_s"]
    assert [m["name"] for m in run.metric_entries(got, cell, True)] == ["toy_share.x"]


def test_every_named_file_of_the_benchmark_exists():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for conf in spec["configs"]:
        assert (run.ROOT / conf["file"]).is_file()
    for cell in spec["workloads"]:
        mix = traffic.load_mix(cell["traffic"])
        assert (run.BENCH / "jobs" / f"{mix['job']}.py").is_file()
        assert run.load_limits(cell["name"], False)
        for m in run.metric_entries(spec, cell, True):
            assert callable(run.load_reader(m["name"]))
