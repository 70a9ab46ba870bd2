"""chip_smoke.py on the CPU: the rehearsal runs green and is labelled cpu,
the real command fails without a chip, and every condition the smoke
passes on is shown to fail when its evidence is doctored.  Plus the
bring-up plumbing the smoke relies on: the backend claim, the compile
cache placement and the content-keyed native build.
"""

import copy
import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, env=None, timeout=600):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, timeout=timeout,
        capture_output=True, text=True,
    )


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One rehearsal run shared by the tests below: (completed process,
    full report).  Run from a scratch dir to catch path assumptions."""
    out = tmp_path_factory.mktemp("smoke")
    report = out / "report.json"
    p = _run(
        [SMOKE, "--platform", "cpu", "--sf", "0.01", "--report", str(report)],
        cwd=str(out),
    )
    assert p.returncode == 0, p.stderr[-4000:]
    with open(report) as f:
        return p, json.load(f)


def test_rehearsal_is_green_and_names_cpu(rehearsal):
    p, report = rehearsal
    lines = p.stdout.strip().splitlines()
    # the last line is the chip check's contract: exactly these keys
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    # the full report is the line before it and ends with the null claim
    full = json.loads(lines[-2])
    assert list(full)[-1] == "claim" and full["claim"] is None
    assert full["checks"] == report["checks"]
    assert report["executor"]["platform"] == "cpu"
    assert report["executor"]["native_partitioner"] == "loaded"
    assert all(report["checks"].values()), report["checks"]
    # the served path really ran: a gang stage on the device path for
    # q1/q6 over every (virtual) device the executor process saw, and
    # the per-task XLA compile accounting reached the job detail
    n_dev = report["executor"]["device_count"]
    for q in ("q1", "q6"):
        first = report["queries"][q]["first"]
        assert first["gang_mesh_devices"] == n_dev, first
        assert first["counters"]["xla_compiles"] > 0
        assert first["device_stages"]
    assert report["queries"]["q3"]["first"]["route"]
    assert report["parent_jax_backends"] == []


def _has_chip() -> bool:
    return bool(glob.glob("/dev/vfio/[0-9]*") or glob.glob("/dev/accel*"))


@pytest.mark.skipif(_has_chip(), reason="this machine has an accelerator")
def test_without_rehearsal_argument_no_chip_fails(tmp_path):
    p = _run([SMOKE, "--sf", "0.01"], cwd=str(tmp_path), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == "", "no result may be printed without a chip"
    assert "executor1 exited" in p.stderr and "tpu" in p.stderr


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _run(["chip_smoke.py", "--platform", "cpu"], cwd=str(tmp_path), env=env)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_every_condition_can_fail(rehearsal):
    import chip_smoke

    _, good = rehearsal
    assert all(chip_smoke.evaluate(copy.deepcopy(good)).values())

    def failed(mutate) -> set:
        r = copy.deepcopy(good)
        mutate(r)
        return {k for k, v in chip_smoke.evaluate(r).items() if not v}

    def run(r, q, label):
        return r["queries"][q][label]

    # a device stage degraded on a device error
    assert "q1_repeat_no_device_error" in failed(
        lambda r: run(r, "q1", "repeat")["counters"].update(device_error=1)
    )
    assert "q3_first_no_device_error" in failed(
        lambda r: run(r, "q3", "first")["counters"].update(device_error=2)
    )
    # an answer differs from the reference
    assert "q3_second_process_matches_reference" in failed(
        lambda r: run(r, "q3", "second_process").update(matches_reference=False)
    )
    # the data routed q6 to the CPU operators: not a device run
    assert "q6_first_ran_on_device" in failed(
        lambda r: run(r, "q6", "first")["counters"].update(cpu_fallback=1)
    )
    assert "q1_first_ran_on_device" in failed(
        lambda r: run(r, "q1", "first").update(device_stages=[])
    )
    # the gang did not span the executor's devices
    assert "q1_first_mesh_devices" in failed(
        lambda r: run(r, "q1", "first").update(gang_mesh_devices=0)
    )
    # the executor's backend is not the one asked for
    assert "executor_platform" in failed(
        lambda r: r.update(asked_platform="tpu")
    )

    # on the chip: a repeat that compiled, a second holder, no cache hit
    def as_tpu(r):
        r["asked_platform"] = "tpu"
        for key in ("executor", "executor_second_process"):
            r[key]["platform"] = "tpu"
        r["chip_holders"] = {
            "first_process": [{"pid": r["executor"]["pid"]}],
            "second_process": [{"pid": r["executor_second_process"]["pid"]}],
        }
        for q in ("q1", "q6", "q3"):
            run(r, q, "repeat")["counters"].pop("xla_compiles", None)
            run(r, q, "repeat")["counters"].pop("kernel_compiles", None)
            run(r, q, "second_process")["counters"]["xla_cache_hits"] = 1

    assert failed(as_tpu) == set()

    def second_holder(r):
        as_tpu(r)
        r["chip_holders"]["first_process"].append({"pid": 1})

    assert failed(second_holder) == {"only_executor_holds_chip"}

    def repeat_compiled(r):
        as_tpu(r)
        run(r, "q6", "repeat")["counters"]["xla_compiles"] = 1

    assert failed(repeat_compiled) == {"q6_repeat_compiled_nothing"}

    def cold_cache(r):
        as_tpu(r)
        for q in ("q1", "q6", "q3"):
            run(r, q, "second_process")["counters"].pop("xla_cache_hits")

    assert failed(cold_cache) == {"second_process_hit_compile_cache"}
    assert "children_exited_cleanly_on_sigterm" in failed(
        lambda r: r["children_exit"]["executor1"].update(sigkill=True)
    )
    assert "parent_touched_no_backend" in failed(
        lambda r: r.update(parent_jax_backends=["tpu"])
    )
    assert "within_time_limit" in failed(lambda r: r.update(seconds=1201))


def test_resolve_backend_refuses_another_platform(monkeypatch):
    from arrow_ballista_tpu.utils import resolve_backend

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert resolve_backend()["platform"] == "cpu"
    # told to use the TPU, got the CPU: an error, never a quiet CPU run
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="asks for 'tpu'"):
        resolve_backend()


def test_compile_cache_is_placed_from_outside(tmp_path):
    code = (
        "import arrow_ballista_tpu.ops, jax;"
        "print(jax.config.jax_compilation_cache_dir)"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    p = _run(["-c", code], cwd=str(tmp_path), env=env)
    assert p.stdout.strip() == os.path.join(REPO, ".jax_cache"), p.stderr
    # set from outside: jax reads it itself, the program sets no other
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    p = _run(["-c", code], cwd=str(tmp_path), env=env)
    assert p.stdout.strip() == str(tmp_path / "cache"), p.stderr


def test_native_build_is_keyed_by_content_and_survives_a_race(tmp_path):
    """Four processes import the partitioner against an EMPTY build dir at
    once (executor + task-runner children do): every one loads a working
    library, and the binary's name changes with the source."""
    pkg = tmp_path / "native"
    shutil.copytree(
        os.path.join(REPO, "arrow_ballista_tpu", "native"), pkg,
        ignore=shutil.ignore_patterns("build", "__pycache__"),
    )
    code = (
        "import importlib.util, sys;"
        f"spec = importlib.util.spec_from_file_location('n', r'{pkg}/__init__.py');"
        "n = importlib.util.module_from_spec(spec); spec.loader.exec_module(n);"
        "print(n.status(), n._so_path())"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code], stdout=subprocess.PIPE, text=True
        )
        for _ in range(4)
    ]
    outs = [p.communicate(timeout=180)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    assert [o[0] for o in outs] == ["loaded"] * 4, outs
    assert len({o[1] for o in outs}) == 1
    built = os.listdir(pkg / "build")
    assert built == [os.path.basename(outs[0][1])], built  # no temp litter
    with open(pkg / "partitioner.cc", "a") as f:
        f.write("\n// changed\n")
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=180
    )
    status, path = p.stdout.split()
    assert status == "loaded" and path != outs[0][1]
