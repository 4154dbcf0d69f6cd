"""
chip_smoke.py on the CPU: its phases are functions of their shapes, so
this file calls them small (Pallas kernels interpreted, four virtual
devices for the four-chip phase) to find wrong paths, arguments and
control flow before a chip is spent on them — and checks that the
SCRIPT itself can never report success from a CPU, from a moved fault
counter, from a fallback warning or past a phase that raised.
"""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402


def _search():
    # budgets for THIS shape: 400-row test folds (one flip = 2.5e-3),
    # and an f32 solve whose stopping error the tiny problem magnifies
    return cs.phase_search(seed=0, n=2000, d=128, k=4, n_candidates=6,
                           accuracy_budget=0.02, logloss_budget=1e-3)[0]


def _forest():
    return cs.phase_forest(seed=2, n=3000, d=28, n_estimators=8,
                           max_depth=4, sample_rows=3000,
                           hist_mode="matmul")[0]


def _boosting():
    return cs.phase_boosting(seed=2, n=3000, d=28, max_iter=5, max_depth=3)


def _sparse():
    return cs.phase_sparse(*cs.make_text_shaped(0, 600, 512, 4))


def _kernels():
    return cs.phase_kernels(
        seed=5, hist_shape=(700, 5, 8, 4, 2), interpret=True,
        pallas_forest=(300, 4, 3))


def _predict_and_serve():
    result, model = cs.phase_batch_predict(seed=3, n_rows=5000)
    return {"batch_predict": result,
            "serving": cs.phase_serving(model, request_rows=(1, 17, 64),
                                        repeats=2)}


def _four_chips():
    import jax

    return cs.phase_four_chips(seed=0, n=600, d=128, k=4, n_candidates=4,
                               devices=jax.devices()[:4])


@pytest.mark.parametrize("phase", [
    _search, _forest, _boosting, _sparse, _kernels, _predict_and_serve,
], ids=lambda f: f.__name__.strip("_"))
def test_phase_runs_small_on_cpu(phase, capsys):
    """Each phase end to end at a tiny size, through ``run_phases`` —
    the same strict warnings and fault-counter check the chip run has."""
    cs.run_phases([("tiny", phase)])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "tiny" and line["seconds"] >= 0
    assert all(v == 0 for v in line["faults"].values())


def test_four_chip_phase_on_four_virtual_devices(capsys):
    """The four-chip phase end to end, and what it reads in the
    programs each layout compiled (once per process — a second call
    would hit the AOT memo and see none): a float all-reduce over PAIRS
    of devices only on the 2x2 tasks x data mesh, none at all on one
    device, and task shards on every device."""
    cs.run_phases([("four", _four_chips)])
    layouts = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])["layouts"]
    assert "f32/2" in layouts["tasks_x_data_2x2"][
        "all_reduces_in_compiled_text"]
    assert not any(k.startswith("f32") for k in layouts["tasks_1d"][
        "all_reduces_in_compiled_text"])
    assert layouts["one_device"]["all_reduces_in_compiled_text"] == {}
    assert len(layouts["tasks_1d"]["task_shard_devices"]) == 4
    assert len(layouts["one_device"]["task_shard_devices"]) == 1


# ---------------------------------------------------------------------------
# the script cannot say "ok" for anything but a clean chip run
# ---------------------------------------------------------------------------

def _stdout_lines(capsys):
    return [json.loads(ln) for ln in
            capsys.readouterr().out.strip().splitlines()]


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one_chip", "four_chips"])
def test_main_refuses_a_cpu(argv, capsys):
    assert cs.main(argv) == 1
    lines = _stdout_lines(capsys)
    assert lines[-1]["ok"] is False and "not a TPU" in lines[-1]["reason"]
    assert not any(ln.get("ok") is True for ln in lines)


def test_script_exits_nonzero_without_a_tpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``, as the driver's
    sandbox runs it: a non-zero exit and never ``"ok": true``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"] is False


@pytest.mark.parametrize("counter", cs.WATCHED_FAULTS)
def test_moved_fault_counter_fails_the_run(counter, capsys):
    from skdist_tpu.parallel import faults

    later = []

    def absorbs_a_fault():
        faults.record(counter)
        return {}

    with pytest.raises(cs.SmokeFailure, match=counter):
        cs.run_phases([("faulty", absorbs_a_fault),
                       ("later", lambda: later.append(1) or {})])
    assert not later
    faults.reset_stats()
    assert _stdout_lines(capsys)[-1]["faults"][counter] == 1


def _warn_fit_failed():
    from skdist_tpu.distribute.search import FitFailedWarning

    warnings.warn("Estimator fit failed; score set to nan.",
                  FitFailedWarning)


@pytest.mark.parametrize("raise_it", [
    _warn_fit_failed,
    lambda: warnings.warn(
        "compacted iterative dispatch exhausted device memory; falling "
        "back to the classic batched path at round_size=8"),
    lambda: warnings.warn(
        "batched_map round exhausted device memory; resuming at "
        "round_size=8 (pass partitions=2 to pick this up front)"),
    lambda: warnings.warn(
        "compile_cache export layer disabled for this program "
        "(ValueError: x); falling back to direct compilation"),
], ids=["fit_failed", "classic_fallback", "oom_shrink", "export_tier"])
def test_fallback_warning_fails_the_run(raise_it):
    """The warnings of search.py, backend.py and compile_cache.py that
    mean the work left its path are errors inside a phase; the
    backend's proactive round sizing notice is not."""
    with pytest.raises(Warning):
        cs.run_phases([("leaves_its_path", lambda: raise_it() or {})])


def test_proactive_round_sizing_notice_is_printed_not_fatal(capsys):
    def sized():
        warnings.warn("batched_map: compiled round footprint ~9000 MiB "
                      "exceeds 8000 MiB free; starting at round_size=64 "
                      "(pass partitions to override)")
        return {}

    cs.run_phases([("sized", sized)])
    assert "starting at round_size=64" in _stdout_lines(
        capsys)[-1]["warnings"][0]


def test_raising_phase_ends_the_run(capsys):
    later = []

    def raises():
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    with pytest.raises(RuntimeError, match="Mosaic"):
        cs.run_phases([("raises", raises),
                       ("later", lambda: later.append(1) or {})])
    assert not later and capsys.readouterr().out == ""


def test_failed_check_prints_what_was_measured(capsys):
    """A check that fails with the phase's partial results prints them
    on the failure's line — and still ends the run."""
    def fails():
        cs.check(False, "layouts disagree", {"layouts": {"a": 1}})

    with pytest.raises(cs.SmokeFailure, match="layouts disagree"):
        cs.run_phases([("four", fails)])
    (line,) = _stdout_lines(capsys)
    assert line == {"phase": "four", "failed": "layouts disagree",
                    "layouts": {"a": 1}}


def test_host_path_is_detected():
    """A search or predict that fell to host threads leaves no round
    stats of its task count on the backend."""
    with pytest.raises(cs.SmokeFailure, match="host path"):
        cs.check_rounds(None, 480, "search")
    with pytest.raises(cs.SmokeFailure, match="host path"):
        cs.check_rounds({"tasks": 16, "retries": 0}, 480, "search")
    with pytest.raises(cs.SmokeFailure, match="retried"):
        cs.check_rounds({"tasks": 480, "retries": 1}, 480, "search")
    cs.check_rounds({"tasks": 480, "retries": 0}, 480, "search")


def test_seeded_data_is_reproducible():
    a = cs.make_text_shaped(3, 50, 64, 3)
    b = cs.make_text_shaped(3, 50, 64, 3)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
