"""The benchmark harness under benchmarks/chip: cells resolve to their
files by name, a new cell is new files only, the traffic is fixed by its
seed, and the command refuses to run without a chip."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from harness import cell as C
from harness import serve, traffic
from test_bench_weights import MOE, assert_the_programs_tree

REPO = C.REPO
RUN = os.path.join(C.HERE, "run.py")


def test_every_cell_resolves_to_its_files():
    bench = C.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    for w in bench["workloads"]:
        c = C.resolve(bench, w["name"])
        assert c.config["name"] == w["config"]
        assert c.traffic["loop"] == "closed"
        assert c.config["reference"]
        C.reference(c.config["reference"]).Reference  # noqa: B018
        assert callable(C.weights(c.config["weights"]).program_params)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert callable(C.reader(m["name"]))
            assert m["moves"] in names, (w["name"], m["name"])
    for conf in bench["configs"]:
        with open(os.path.join(REPO, conf["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == conf["reduced"]
        for key in ("source", "deployment", "assumed", "model", "engine"):
            assert key in body, (conf["name"], key)


@pytest.mark.parametrize("weights", ["moe_lm", None, "no_such_module"])
def test_a_cell_added_as_new_files_loads(weights, tmp_path, monkeypatch):
    """A new configuration, its weights module, mix and per-layer metric
    are new files plus new entries in BENCHMARK.json; no existing file
    changes.  The configuration names a weights module that makes the
    program's expert tree; one that names no module, or one that is not
    there, is an error at resolve."""
    here = tmp_path / "chip"
    shutil.copytree(C.HERE, here, ignore=shutil.ignore_patterns("_runs"))
    bench = C.load_benchmark()
    shutil.copy(os.path.join(os.path.dirname(__file__), "moe_lm.py"),
                here / "weights" / "moe_lm.py")
    conf = json.loads((here / "configs" / "camformer-bert-f32.json")
                      .read_text())
    conf.update(name="new-model", model=MOE)
    conf.pop("weights")
    if weights:
        conf["weights"] = weights
    (here / "configs" / "new-model.json").write_text(json.dumps(conf))
    (here / "traffic" / "new-mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 3,
         "prompt": {"dist": "uniform", "lo": 8, "hi": 9},
         "max_new": {"dist": "uniform", "lo": 2, "hi": 3},
         "sampling": {"temperature": 0.7, "top_k": 50}}))
    (here / "metrics" / "new_metric.decode.py").write_text(
        "def read(w):\n    return 42.0\n")
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "benchmarks/chip/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.cell", "config": "new-model",
                               "traffic": "new-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "new_metric.decode", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "itl_p95_ms",
                               "workloads": ["new.cell"]})
    monkeypatch.setattr(C, "HERE", str(here))
    if weights != "moe_lm":
        with pytest.raises(ValueError, match="names (no|the) weights module"):
            C.resolve(bench, "new.cell")
        return
    c = C.resolve(bench, "new.cell")
    assert c.traffic["clients"] == 3
    assert [m["name"] for m in c.per_layer] == ["new_metric.decode"]
    assert C.reader("new_metric.decode")(None) == 42.0
    assert {m["name"] for m in c.end_to_end} == {
        "tokens_per_s", "itl_p95_ms", "setup_s"}
    W = C.weights(c.config["weights"])
    assert W.__file__ == str(here / "weights" / "moe_lm.py")
    assert_the_programs_tree(W, c.config["model"])


MIX = {"loop": "closed", "clients": 16, "steady_first_wave": True,
       "prompt": {"dist": "uniform", "lo": 64, "hi": 256},
       "max_new": {"dist": "uniform", "lo": 512, "hi": 768},
       "sampling": {"temperature": 0.7, "top_k": 50}, "greedy_every": 8}


def _dump(reqs):
    return [(r.prompt, r.max_new, r.greedy) for r in reqs]


@pytest.mark.parametrize("first_wave", [False, True])
def test_traffic_is_fixed_by_the_seed(first_wave):
    mix = dict(MIX, steady_first_wave=first_wave)
    seed = 2**33 + 1
    a, b = traffic.Traffic(mix, seed, 30522), traffic.Traffic(mix, seed,
                                                              30522)
    assert _dump(a.requests(100)) == _dump(b.requests(100))
    other = traffic.Traffic(mix, seed + 1, 30522).requests(100)
    assert _dump(other) != _dump(traffic.Traffic(mix, seed,
                                                 30522).requests(100))
    # every seed does the same work: the same lengths, in another order
    reqs = traffic.Traffic(mix, seed, 30522).requests(64)
    reqs2 = traffic.Traffic(mix, seed + 7, 30522).requests(64)
    assert sorted(len(r.prompt) for r in reqs) == sorted(
        len(r.prompt) for r in reqs2)
    assert sorted(r.max_new for r in reqs) == sorted(r.max_new
                                                     for r in reqs2)
    assert sum(r.greedy for r in reqs) == 8
    assert len({r.prompt[0] for r in reqs}) == 64  # nothing shared
    waves = [traffic.Traffic(mix, s, 30522).first_wave() for s in
             (seed, seed, seed + 1)]
    assert _dump(waves[0]) == _dump(waves[1]) != _dump(waves[2])


def test_first_wave_is_staggered_and_the_same_work_for_every_seed():
    wave = traffic.Traffic(MIX, 3, 30522).first_wave()
    flat = traffic.Traffic(dict(MIX, steady_first_wave=False), 3,
                           30522).first_wave()
    assert len(wave) == MIX["clients"]
    assert all(w.max_new >= 1 for w in wave)
    assert len({w.max_new for w in wave}) > 1
    # the moved tokens come out of the outputs and into the prompts
    assert sum(len(w.prompt) + w.max_new for w in wave) == sum(
        len(f.prompt) + f.max_new for f in flat)
    # every seed sends the same (prompt, output) pairs, to other clients
    other = traffic.Traffic(MIX, 4, 30522).first_wave()
    pairs = lambda ws: [(len(w.prompt), w.max_new) for w in ws]
    assert sorted(pairs(other)) == sorted(pairs(wave))
    assert pairs(other) != pairs(wave)


class _FakeRunner:
    def __init__(self):
        self.live = []

    def submit(self, req, sink):
        self.live.append((req, sink))


def test_closed_loop_keeps_exactly_n_clients():
    from repro.serving.request import RequestOutput, RequestState

    runner = _FakeRunner()
    gen = traffic.Traffic(MIX, 5, 30522)
    clients = serve.Clients(runner, gen)
    clients.start_closed(gen.first_wave())
    rng = np.random.default_rng(0)
    for step in range(200):
        assert len(runner.live) == MIX["clients"]
        req, sink = runner.live.pop(int(rng.integers(len(runner.live))))
        sink(RequestOutput(rid=step, token=1, index=1,
                           state=RequestState.FINISHED, finished=True,
                           finish_reason="length", tokens=(1,)))
    assert len(clients.records) == MIX["clients"] + 200
    assert sorted({r.client for r in clients.records}) == list(
        range(MIX["clients"]))
    clients.stop()
    _, sink = runner.live.pop()
    sink(RequestOutput(rid=999, token=1, index=1,
                       state=RequestState.FINISHED, finished=True,
                       finish_reason="length", tokens=(1,)))
    assert len(runner.live) == MIX["clients"] - 1


def _run(cwd, script):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, script, "--workload", "bert-cam.decode", "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(r):
    lines = r.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_refuses_to_run_on_the_cpu():
    r = _run(REPO, RUN)
    assert r.returncode != 0, r.stdout
    assert "needs a TPU" in r.stderr, r.stderr
    assert _no_result(r), r.stdout


def test_refuses_to_run_with_only_the_benchmark_files(tmp_path):
    """A checkout that holds BENCHMARK.json and the files under ``paths``
    but not the program exits non-zero with no result."""
    bench = C.load_benchmark()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("_runs",
                                                      "__pycache__"))
    r = _run(str(tmp_path), str(tmp_path / "benchmarks" / "chip" / "run.py"))
    assert r.returncode != 0, r.stdout
    assert _no_result(r), r.stdout


def test_importing_the_harness_loads_no_libtpu():
    code = (
        "import sys, glob\n"
        f"sys.path.insert(0, {C.HERE!r})\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'src')!r})\n"
        "import run, control\n"
        "from harness import cell, check, counts, serve, tracefile, "
        "traffic, window\n"
        "b = cell.load_benchmark()\n"
        "for m in b['per_layer']: cell.reader(m['name'])\n"
        "import json\n"
        "for c in b['configs']:\n"
        f"    conf = json.load(open({REPO!r} + '/' + c['file']))\n"
        "    cell.reference(conf['reference'])\n"
        "    cell.weights(conf['weights'])\n"
        "maps = open('/proc/self/maps').read()\n"
        "print('libtpu' in maps)\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "False"
