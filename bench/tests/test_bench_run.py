"""bench/run.py: refuses the CPU, needs the program, and, rehearsed on the
CPU at smoke size through its own entry ``run_cell`` (the only way past
its look for a chip), decides ``correct`` as it should: true for the program
as it is, false for a token altered where it is produced, and the fp8
control, through the same check, does not come out correct.  A cell whose
engine is sharded over a mesh is rehearsed on four virtual CPU devices in a
child process (``mesh_cell.py``)."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_smoke import BENCH, ROOT, RUN, benchmark, copy_uploads, \
    read_json, smoke  # noqa: E402

WORKLOADS = benchmark()["workloads"]
CELLS = [w["name"] for w in WORKLOADS]
CHIPS = {w["name"]: w["chips"] for w in WORKLOADS}
MESH_CELLS = [w for w in CELLS if CHIPS[w] > 1]
MESH_TIMEOUT = 600
SEED = 2**31 + 99
# At the medium smoke size below (d_model 256, 4 layers, vocabulary 8192,
# bf16) the program's widest gap read at most 1e-4 over seeds 1-3 and the
# fp8 control's at least 0.06: the limit sits between, nearer the program.
MEDIUM = dict(d_model=256, n_layers=4, vocab=8192)
MEDIUM_LIMIT = 0.02


@pytest.fixture(autouse=True)
def chip_upload_semantics(monkeypatch):
    """Rehearse with uploads that copy, as they do to a TPU (see
    ``bench_smoke.CopyingUploads``)."""
    copy_uploads(monkeypatch)


def result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return [o for o in out if isinstance(o, dict) and "correct" in o]


def run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         CELLS[0], "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def mesh_cell(*args):
    """``mesh_cell.py`` in a child process on four virtual CPU devices;
    its result line."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "mesh_cell.py"),
         *map(str, args)], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=MESH_TIMEOUT)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cli_refuses_the_cpu_and_prints_no_result():
    p = run_cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert result_lines(p.stdout) == [] and p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_cli_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(str(tmp_path))
    assert p.returncode != 0
    assert result_lines(p.stdout) == []


def test_every_name_finds_its_file():
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        cfg = read_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(BENCH, "models",
                                           cfg["family"] + ".py"))
    for w in bench["workloads"]:
        spec = RUN.cell_of(bench, w["name"])
        assert spec["end_to_end"] and spec["per_layer"]
        assert "setup_s" in [m["name"] for m in spec["end_to_end"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(RUN.reader(m["name"]), "read")
        if "moves" in m:
            assert m["moves"] in e2e
            for cell in m.get("workloads", CELLS):
                names = [x["name"] for x in
                         RUN.cell_of(bench, cell)["end_to_end"]]
                assert m["moves"] in names, (m["name"], cell)


def test_a_split_metric_is_read_by_its_base_reader():
    base, split = RUN.reader("step_mfu"), RUN.reader("step_mfu.ttft")
    assert split.read.__code__.co_code == base.read.__code__.co_code
    with pytest.raises(FileNotFoundError):
        RUN.reader("no_such_metric.ttft")


def test_a_program_model_of_other_widths_is_refused():
    spec, pc = smoke(CELLS[0])
    RUN.Harness(spec["cfg"], pc)
    for other in (pc.with_(d_ff=pc.d_ff + 16), pc.with_(qk_norm=not pc.qk_norm)):
        with pytest.raises(RuntimeError, match="differs from the configuration"):
            RUN.Harness(spec["cfg"], other)


def test_program_options_set_only_constants_of_the_arithmetic():
    spec, pc = smoke(CELLS[0])
    cfg = dict(spec["cfg"], program_options={"norm_eps": 1e-5})
    assert RUN.program_model(cfg, pc).norm_eps == 1e-5
    for opts in ({"d_ff": pc.d_ff}, {"qkv_bias": pc.qkv_bias},
                 {"partial_rotary": pc.partial_rotary}):
        with pytest.raises(RuntimeError, match="program_options may set"):
            RUN.program_model(dict(cfg, program_options=opts), pc)


def test_peaks_table_refuses_an_unknown_device():
    assert RUN.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(RuntimeError, match="no peaks"):
        RUN.peaks_of("TPU v9 imaginary")


def test_a_mesh_must_span_the_cells_chips(tmp_path):
    bench = benchmark()
    for w in MESH_CELLS:
        conf = {c["name"]: c for c in bench["configs"]}[
            RUN.cell_of(bench, w)["cell"]["config"]]
        cfg = read_json(os.path.join(ROOT, conf["file"]))
        assert RUN.chips_of(cfg) == CHIPS[w]
        cfg["engine"]["mesh"] = {"data": 1, "model": 2}
        path = tmp_path / "cfg.json"
        path.write_text(json.dumps(cfg))
        other = dict(bench, configs=[dict(conf, file=str(path))])
        with pytest.raises(SystemExit, match="its engine spans 2"):
            RUN.cell_of(other, w)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_of_each_cell_is_correct(workload):
    spec, pc = smoke(workload)
    if CHIPS[workload] > 1:
        out = mesh_cell("run", workload, SEED, 2.0)
    else:
        out = RUN.run_cell(spec, SEED, 2.0, False, jax.devices(), pcfg=pc)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in spec["end_to_end"]}
    assert set(out["metrics"]) == names
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= CHIPS[workload]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", MESH_CELLS)
def test_token_altered_where_produced_is_not_correct_on_the_mesh(workload):
    out = mesh_cell("run", workload, SEED, 2.0, "--alter")
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", MESH_CELLS)
def test_weights_are_drawn_in_the_programs_shards(workload):
    out = mesh_cell("draw", workload)
    # every weight drawn laid out as the program's parameter that holds it,
    # the engine serving those same buffers, and each device holding its
    # share: the matrices split over the model axis, norms whole
    assert out["sharded_as_program"] and out["engine_shares_buffers"]
    assert max(out["device_bytes"]) < 0.55 * out["total_bytes"]


@pytest.fixture(scope="module")
def medium():
    spec, pc = smoke(CELLS[0], **MEDIUM)
    spec["cfg"]["limits"]["max_logit_gap"] = MEDIUM_LIMIT
    return spec, pc


def test_medium_rehearsal_is_correct(medium):
    spec, pc = medium
    out = RUN.run_cell(spec, SEED, 3.0, False, jax.devices(), pcfg=pc)
    assert out["correct"], out["checks"]
    assert out["checks"]["compared_tokens"]["value"] > 0


def test_token_altered_where_produced_is_not_correct(medium, monkeypatch):
    spec, pc = medium
    load = RUN.Harness.load

    def broken_load(self, seed):
        load(self, seed)
        fns = self.ev.fns
        decode = fns["decode_multi"]

        def altered(*a, **kw):
            toks, arena, nxt, ln = decode(*a, **kw)
            return (toks + 1) % self.m["V"], arena, nxt, ln
        fns["decode_multi"] = altered
    monkeypatch.setattr(RUN.Harness, "load", broken_load)
    out = RUN.run_cell(spec, SEED, 3.0, False, jax.devices(), pcfg=pc)
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_fails_the_limit_the_program_meets(medium):
    import control
    spec, pc = medium
    h = RUN.Harness(spec["cfg"], pc)
    for seed in (11, 12):
        r = control.readings(h, spec["traffic"], seed, 3.0)
        assert r["program_correct"], r["program"]
        assert not r["control_correct"], r["control"]
        gap = r["control"]["max_logit_gap"]
        assert gap["limit"] == spec["cfg"]["limits"]["max_logit_gap"]
        assert gap["value"] > gap["limit"]
        assert r["program"]["compared_tokens"] == r["control"]["compared_tokens"]
