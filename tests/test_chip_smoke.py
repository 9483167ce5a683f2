"""chip_smoke.py's contract, as far as a CPU sandbox can hold it to: no TPU
means a non-zero exit before any data is made and no result line; only the
explicit rehearsal flag runs it on the CPU, and it says so on every line."""
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, cwd=_REPO):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_without_a_tpu_it_fails_before_generating_data():
    res = _run()
    assert res.returncode != 0
    assert "no TPU was found" in res.stderr
    assert "platform=cpu" in res.stdout
    assert "datagen" not in res.stdout
    assert '"ok"' not in res.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script is not the system: copied out of the repo it must not
    report a pass."""
    import shutil
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    res = _run("--rehearse-cpu", "--scale", "0.002", cwd=str(tmp_path))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_rehearsal_runs_every_leg_and_says_so_on_every_line(tmp_path):
    res = _run("--rehearse-cpu", "--scale", "0.002", "--warm", "2",
               "--out", str(tmp_path / "out"))
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    lines = res.stdout.strip().splitlines()
    assert all("REHEARSAL platform=cpu" in ln for ln in lines), lines
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    for leg in ("q6:", "q1:", "q3:", "q5:", "q13:", "scan leg:",
                "eager leg:", "serving leg:"):
        assert any(leg in ln for ln in lines), leg
    report = json.load(open(tmp_path / "out" / "chip_smoke.json"))
    assert report["rehearsal"] is True
    assert sorted(report["queries"]) == ["q1", "q13", "q3", "q5", "q6"]
    # the scan leg's table does not ride back with the report
    assert os.listdir(tmp_path / "out") == ["chip_smoke.json"]


def test_bench_exits_nonzero_on_a_query_error_after_printing_its_json():
    """bench.py no longer reports a broken query with exit 0: the JSON
    still comes first, the exit code says whether to believe it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3"}
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), "0.002",
         "--queries", "q6", "--conf",
         "spark.rapids.tpu.test.faults=execute:error:always"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 1
    doc = json.loads(res.stdout.strip().splitlines()[-1])
    assert doc["final"] is True and doc["errors"] == 1
    assert "InjectedQueryError" in doc["tpch_suite_queries"]["q6"]["error"]
    assert "FAILED" in res.stderr
