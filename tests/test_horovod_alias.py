"""`horovod` compatibility alias tests (BASELINE.md north star:
reference scripts running UNMODIFIED).

Reference analog: the reference's own public import surface
(horovod/__init__.py + framework submodules, SURVEY.md §2.3) and its
`horovodrun` CLI (§2.4).  The alias package must hand back the SAME
module objects as horovod_tpu (no duplicated singleton state), and a
verbatim reference-style training script must train under a
``horovodrun -np 2`` console script with zero edits.
"""

import os
import stat
import subprocess
import sys


import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "examples", "compat",
                      "pytorch_mnist_unmodified.py")


def test_alias_shares_module_objects():
    import horovod
    import horovod.torch as hvd_alias

    import horovod_tpu
    import horovod_tpu.torch as hvd_real

    assert hvd_alias is hvd_real
    # deep submodules too — a separate module instance would duplicate
    # handle tables and controller singletons
    import horovod.torch.elastic as a_el
    import horovod_tpu.torch.elastic as r_el

    assert a_el is r_el
    assert horovod.__version__ == horovod_tpu.__version__
    # the reference's flat top-level surface rides along
    assert callable(horovod.init) and callable(horovod.allreduce)


def test_alias_run_module():
    import horovod.run as hrun

    from horovod_tpu import runner

    assert hrun is runner
    # the reference's programmatic launcher lives at horovod.runner.run
    from horovod.runner import run, run_commandline

    assert callable(run) and callable(run_commandline)


def test_alias_missing_backend_parity():
    # horovod.mxnet must fail exactly like horovod_tpu.mxnet does in an
    # image without mxnet — the alias adds no masking layer
    with pytest.raises(ImportError):
        import horovod.mxnet  # noqa: F401


@pytest.mark.integration
def test_unmodified_reference_script_under_horovodrun(tmp_path):
    """The whole north-star sentence, literally: a console script named
    ``horovodrun`` (same entry point the wheel installs) launches the
    unchanged-reference-imports example at -np 2 and it trains."""
    bindir = tmp_path / "bin"
    bindir.mkdir()
    shim = bindir / "horovodrun"
    # exactly what pyproject's [project.scripts] horovodrun resolves to
    shim.write_text(
        "#!" + sys.executable + "\n"
        "import sys\n"
        "from horovod_tpu.runner.launch import run_commandline\n"
        "sys.exit(run_commandline())\n"
    )
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)

    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATH"] = f"{bindir}:{env['PATH']}"
    env.pop("XLA_FLAGS", None)

    out = subprocess.run(
        ["horovodrun", "-np", "2", "--", sys.executable, SCRIPT,
         "--epochs", "2"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "UNMODIFIED_OK" in out.stdout
