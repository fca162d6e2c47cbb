"""Start-up: the platform and the compile cache are placed from
outside, by JAX's own variables, and the library overrides neither.

Every child here ends up on the CPU: a child left to JAX's default
would reach for the TPU library, which one process at a time may hold.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code, **extra):
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout.strip().splitlines()


def test_jax_platforms_env_alone_is_honored():
    """``JAX_PLATFORMS=cpu`` needs no help from the library: no
    ``jax.config`` call, no variable of our own."""
    out = _child("import mxnet_tpu as mx, jax; "
                 "print(jax.default_backend()); "
                 "print(mx.default_context()); "
                 "print(float(mx.np.zeros(3).sum()))")
    assert out == ["cpu", "cpu(0)", "0.0"]


def test_user_config_pin_not_overridden():
    """A ``jax.config.update`` made before importing the library
    survives the import, whatever the environment asks for."""
    out = _child("import jax; "
                 "jax.config.update('jax_platforms', 'cpu'); "
                 "import mxnet_tpu as mx; "
                 "print(jax.config.jax_platforms); "
                 "print(jax.default_backend())",
                 JAX_PLATFORMS="cpu,tpu")
    assert out == ["cpu", "cpu"]


def test_compile_cache_dir_from_environment_wins(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set outside is the directory in
    use — import adopts it, and an entry script's own default cannot
    displace it."""
    out = _child("import mxnet_tpu as mx, jax\n"
                 "from mxnet_tpu import compile_cache as cc\n"
                 "print(cc.cache_dir())\n"
                 "print(cc.configure(cc.CHECKOUT_DIR))\n"
                 "print(jax.config.jax_compilation_cache_dir)\n"
                 "jax.jit(lambda x: x + 1)(1.0).block_until_ready()\n"
                 "print(cc.entry_count() > 0)",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out == [str(tmp_path)] * 3 + ["True"]
    assert any(tmp_path.iterdir())
    assert not os.path.exists(os.path.join(str(tmp_path), "..",
                                           ".jax_compile_cache"))


def test_compile_cache_unset_gives_the_fixed_checkout_path():
    """Unset, the library runs without a persistent cache; the entry
    scripts' directory is one fixed path inside the checkout — no
    tempfile, pid or time in it."""
    out = _child("import mxnet_tpu as mx, jax\n"
                 "from mxnet_tpu import compile_cache as cc\n"
                 "print(cc.cache_dir())\n"
                 "print(jax.config.jax_compilation_cache_dir)\n"
                 "print(cc.CHECKOUT_DIR)")
    assert out == ["None", "None",
                   os.path.join(ROOT, ".jax_compile_cache")]
