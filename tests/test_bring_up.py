"""What the chip bring-up (PR 21) made true and must stay true: no
fallback hides the device, one compile-cache rule, one process per
chip, and a smoke script that refuses to pass off the TPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code, **env):
    e = {k: v for k, v in os.environ.items()
         if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    e.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **env)
    return subprocess.run([sys.executable, "-c", code], env=e, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)


def test_set_device_asks_jax_for_the_named_platform(monkeypatch):
    """No TPU: ``set_device("tpu")`` raises instead of keeping the CPU.
    A TPU default backend: 'cpu' still resolves (``Tensor.cpu()``,
    ``to_tensor(place=CPUPlace())``) — ``jax.devices()`` alone lists
    only the default backend."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.common.errors import InvalidArgumentError
    assert paddle.set_device("cpu").device_type == "cpu"
    with pytest.raises(InvalidArgumentError, match="no 'tpu' device"):
        paddle.set_device("tpu")
    assert paddle.get_device() == "cpu:0"
    assert not paddle.is_compiled_with_tpu()

    class Chip:
        platform, id = "tpu", 0
    real = jax.devices
    monkeypatch.setattr(
        jax, "devices", lambda backend=None:
        [Chip()] if backend in (None, "tpu") else real(backend))
    assert paddle.is_compiled_with_tpu()
    assert paddle.set_device("tpu").jax_device.platform == "tpu"
    assert paddle.set_device("cpu").jax_device is real("cpu")[0]
    with pytest.raises(InvalidArgumentError, match="out of range"):
        paddle.set_device("tpu:1")


_CACHE_PROBE = (
    "import jax, json\n"
    "from paddle_tpu.runtime.compile_cache import enable_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "path = enable_compile_cache()\n"
    "print(json.dumps([before, path, "
    "jax.config.jax_compilation_cache_dir]))\n")


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir_rule(from_env, tmp_path):
    """Env var set: it wins and no directory is set in code.  Unset: one
    fixed path inside the checkout (no pid, time or temp name)."""
    env_dir = str(tmp_path / "cache") if from_env else None
    proc = _child(_CACHE_PROBE, **(
        {"JAX_COMPILATION_CACHE_DIR": env_dir} if env_dir else {}))
    assert proc.returncode == 0, proc.stderr[-2000:]
    before, path, after = json.loads(proc.stdout.strip().splitlines()[-1])
    if env_dir:
        assert before == path == after == env_dir
    else:
        assert before is None
        assert path == after == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_other_place_sets_a_cache_dir():
    hits = []
    for base, _, files in os.walk(ROOT):
        if any(p in base for p in (".git", "chiprun_out", "_lib",
                                   ".jax_cache", "_checkout")):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            with open(path, errors="replace") as f:
                text = f.read()
            if "jax_compilation_cache_dir" in text and not path.endswith(
                    (os.path.join("runtime", "compile_cache.py"),
                     os.path.join("tests", "test_bring_up.py"))):
                hits.append(os.path.relpath(path, ROOT))
            assert "ALLOW_MULTIPLE_LIBTPU" + "_LOAD" not in text, path
    assert hits == []


@pytest.mark.parametrize("host,platforms,refused", [
    ("accel", None, True), ("accel", "tpu,cpu", True),
    ("accel", "cpu", False), ("vfio-tpu", None, True),
    ("vfio-other", None, False), ("none", None, False)])
def test_launcher_refuses_several_workers_on_a_tpu_host(
        monkeypatch, tmp_path, host, platforms, refused):
    """A TPU host is told from its device nodes (never by asking JAX):
    /dev/accel*, or a vfio group holding a function of Google's PCI
    vendor id — a vfio group of some other device is not a TPU."""
    import functools
    from paddle_tpu.distributed.launch import controller as C
    dev, sysfs = tmp_path / "dev", tmp_path / "sys"
    dev.mkdir()
    if host == "accel":
        (dev / "accel0").touch()
    elif host.startswith("vfio"):
        (dev / "vfio").mkdir()
        (dev / "vfio" / "vfio").touch()
        (dev / "vfio" / "3").touch()
        fn = sysfs / "kernel/iommu_groups/3/devices/0000:00:05.0"
        fn.mkdir(parents=True)
        (fn / "vendor").write_text(
            "0x1ae0\n" if host == "vfio-tpu" else "0x10de\n")
    monkeypatch.setattr(C, "_tpu_device_nodes", functools.partial(
        C._tpu_device_nodes, dev=str(dev), sysfs=str(sysfs)))
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    ctl = C.Controller(C.LaunchConfig(script="x.py", nproc_per_node=2))
    if refused:
        with pytest.raises(RuntimeError, match="one process drives all"):
            ctl._refuse_shared_chips()
    else:
        ctl._refuse_shared_chips()
    C.Controller(C.LaunchConfig(script="x.py", nproc_per_node=1)
                 )._refuse_shared_chips()       # one worker: always fine


@pytest.mark.parametrize("error,falls_back", [("ShapeNotCovered", True),
                                              ("RuntimeError", False),
                                              ("NotImplementedError",
                                               False)])
def test_fused_update_falls_back_only_on_shape_not_covered(
        monkeypatch, error, falls_back):
    """A compiler error must fail the step, never become the reference
    math in silence; only the documented signal falls back."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import fused_train as ft
    exc = {"ShapeNotCovered": pallas.ShapeNotCovered,
           "RuntimeError": RuntimeError,
           "NotImplementedError": NotImplementedError}[error]

    def boom(*a, **k):
        raise exc("from the kernel path")
    monkeypatch.setattr(ft, "kernels_active", lambda: True)
    monkeypatch.setattr(ft, "_fused_update_kernel", boom)
    p = jnp.ones((8, 128), jnp.float32)
    call = lambda: ft.fused_update_flat(          # noqa: E731
        "sgd", p, p, {}, lr=0.1, step_f=1.0, clip_scale=None, hyper={})
    if falls_back:
        new_p, _ = call()
        assert float(new_p[0, 0]) == pytest.approx(0.9)
    else:
        with pytest.raises(exc, match="from the kernel path"):
            call()


def test_adam_bias_corrections_ride_the_scalar_operand():
    """Mosaic has no scalar powf: the kernel body must not compute
    ``beta ** t``; the reference and the kernel share one helper."""
    import inspect
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import fused_train as ft
    assert "**" not in inspect.getsource(ft._update_math)
    assert "**" not in inspect.getsource(ft._opt_kernel_body)
    hp = {"beta1": 0.9, "beta2": 0.95}
    bc1, bc2 = ft._bias_corrections("adam", hp, jnp.float32(3.0))
    assert float(bc1) == pytest.approx(1 - 0.9 ** 3)
    assert float(bc2) == pytest.approx(1 - 0.95 ** 3)
    assert ft._bias_corrections("sgd", {}, jnp.float32(3.0)) is None


def test_chip_smoke_refuses_to_run_off_the_tpu():
    """Without options the smoke needs a TPU: non-zero exit and NO
    result line on the CPU (a CPU pass must never read as a chip run)."""
    e = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    e["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=e,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not a" in proc.stderr and "TPU" in proc.stderr
    # and no option picks phases: the result line means all of them ran
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse",
                           "--phases", "none"], env=e, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2 and "unrecognized" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("ulps,equal,total,error", [
    (2, 9, 10, None),
    (3, 9, 10, "not bf16 ties"),
    (1, 7, 10, "fewer than 80%"),
    (1, 8, 10, "never compared")])
def test_chip_smoke_tie_rule(ulps, equal, total, error):
    """The smoke's token rule: a difference passes only as a bf16 tie
    (both candidates within TIE_ULPS ulps of an independent forward's
    top logit), every position is compared, most are equal outright."""
    import numpy as np
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke as cs
    finally:
        sys.path.remove(ROOT)
    assert cs.TIE_ULPS == 2 and cs.bf16_ulp(3.5) == 2.0 ** -6
    diffs = []
    eq, follow = cs.split_at_difference(
        [(0, [7], [1, 2, 3, 4], [1, 2, 9, 4]), (1, [8], [5, 6], [5, 6])],
        diffs)
    assert eq == 4 and follow == [(0, [7, 1, 2, 9], [4])]
    assert diffs == [{"request": 0, "context": [7, 1, 2], "got": 3,
                      "want": 9}]

    class Judge:
        def logits_after(self, context):
            assert context == [7, 1, 2]
            logits = np.zeros(16, np.float32)
            logits[3] = 3.5
            logits[9] = 3.5 - ulps * 2.0 ** -6
            return logits
    cmp = {"equal": equal, "total": total,
           "diffs": diffs * (total - equal if error != "never compared"
                             else 1)}
    if error is None:
        cs.judge_ties("a vs b", cmp, Judge())
    else:
        with pytest.raises(AssertionError, match=error):
            cs.judge_ties("a vs b", cmp, Judge())


def test_importing_the_package_initialises_no_backend():
    proc = _child(
        "import paddle_tpu, jax\n"
        "from jax._src import xla_bridge\n"
        "print(len(xla_bridge._backends))\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "0"
