"""chip_smoke.py off the chip: it must fail, stay off jax, and build command
lines the launchers accept. What it proves ON the chip is the driver's run."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_no_tpu_exits_nonzero_without_the_ok_line():
    """With no accelerator the first child (``--platform tpu``) dies at
    backend start: non-zero exit, no training step, no contract line."""
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    last = out.stdout.strip().splitlines()[-1]
    assert '"ok"' not in last
    assert "CHIP SMOKE FAILED" in out.stdout
    assert "[sl]" not in out.stdout and "[rl]" not in out.stdout  # never got there


def test_parent_never_imports_jax():
    """A parent that has touched jax holds the chip its children need."""
    code = (
        "import sys, chip_smoke as cs\n"
        "cs.sl_cmd('x'); cs.rl_cmd('x'); cs.kernels_cmd()\n"
        "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules, "
        "sorted(m for m in sys.modules if m.startswith('jax'))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_phase_command_lines_parse_under_the_launchers():
    import chip_smoke as cs
    from distar_tpu.bin import rl_train, sl_train
    from distar_tpu.utils import read_config

    def argv(cmd, module):
        assert cmd[:4] == [sys.executable, "-u", "-m", module]
        return cmd[4:]

    for extra in ((), ("--batch-size", "4", "--mesh", "dp=1"),
                  ("--batch-size", "4", "--mesh", "dp=2,fsdp=2")):
        args = sl_train.build_parser().parse_args(
            argv(cs.sl_cmd("/tmp/x", *extra), "distar_tpu.bin.sl_train"))
        assert (args.type, args.smoke_model, args.no_supervise, args.platform) == (
            "learner", False, True, "tpu")
        assert args.iters == cs.ITERS and args.iters // 4 == 1  # export every iteration
    args = rl_train.build_parser().parse_args(
        argv(cs.rl_cmd("/tmp/x"), "distar_tpu.bin.rl_train"))
    assert (args.type, args.smoke_model, args.no_supervise, args.platform) == (
        "all", False, True, "tpu")
    # the committed configs: flagship widths (nothing under model but the
    # dtype), bf16, and the sizes the launchers read
    for path, blocks in ((cs.SL_CONFIG, ("learner",)), (cs.RL_CONFIG, ("learner", "actor"))):
        cfg = read_config(os.path.join(REPO, path))
        assert dict(cfg["model"]) == {"dtype": "bfloat16"}
        assert all(b in cfg for b in blocks)
        assert cfg["learner"]["unroll_len"] == 64
    assert os.path.exists(os.path.join(REPO, cs.kernels_cmd()[2]))
