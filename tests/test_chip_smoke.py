"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, its
control flow completes on the 8-device CPU mesh at a tiny size (kernels
interpreted), the compile cache goes where it should, and a merge
backend that cannot be built is an error, not a host path."""

import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from geomx_tpu.core.config import Config, Topology  # noqa: E402
from geomx_tpu.utils import compile_cache  # noqa: E402

# every width cut; attention is "fast" because the TPU interpreter
# deadlocks when two worker threads run interpreted kernels at once (see
# test_block_attention's grads test) — the kernels are checked
# interpreted on the main thread, and uninterpreted on the chip
TINY = chip_smoke.SmokeConfig(
    vocab=64, d_model=32, n_heads=2, d_ff=64, max_seq=32, batch=4,
    attn_impl="fast", n_layers=2, steps=3, lr=3e-3, mpq_size_bound=1000,
    merge_backend="jax", flash_shape=(1, 256, 2, 128),
    hop_shape=(1, 64, 2, 128), codec_elems=5000, interpret_kernels=True)


@pytest.fixture
def no_cache_side_effect():
    """``main()`` configures the persistent cache; put jax back as it was."""
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_main_refuses_to_run_off_chip(capsys, no_cache_side_effect):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "platform='cpu'" in str(exc.value.code)
    out = capsys.readouterr().out
    assert "platform=cpu" in out          # says what it found
    assert '"ok"' not in out              # and prints no result


@pytest.mark.parametrize("n_devices", [1, 4])
def test_run_completes_both_layouts_on_cpu_mesh(n_devices, capsys):
    chip_smoke.run(TINY, jax.devices()[:n_devices])
    out = capsys.readouterr().out
    layout = ("2 parties sharing" if n_devices == 1
              else "2 parties x 2-chip dp mesh")
    assert out.count(layout) == 2          # the FSA and the MPQ phase
    assert ("ring attention" in out) == (n_devices == 4)
    assert "parties identical" in out and "servers on jax/cpu" in out


def test_pick_depth_cuts_to_what_the_chip_holds():
    flagship = chip_smoke.SmokeConfig()
    deep, _ = chip_smoke.pick_depth(flagship, 10**12)
    v5e, why = chip_smoke.pick_depth(flagship, 16 * 2**30)
    assert deep == 8 and 1 <= v5e < 8
    assert f"L{v5e} of 8" in why


def test_cache_dir_comes_from_env_when_set(monkeypatch,
                                           no_cache_side_effect):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    # nothing was set in code: jax reads the variable itself
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch,
                                                   no_cache_side_effect):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_merge_backend_raises_when_jax_backend_cannot_be_built(monkeypatch):
    from geomx_tpu.kvstore import backend, jax_backend

    def broken(self, config=None, tracer=None):
        raise RuntimeError("device backend failed to initialize")

    monkeypatch.setattr(jax_backend.JaxBackend, "__init__", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        backend.make_merge_backend(
            Config(topology=Topology(), merge_backend="jax"))


def test_accelerator_probe_lets_backend_init_errors_through(monkeypatch):
    from geomx_tpu.kvstore import backend

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    for var in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "GEOMX_MERGE_BACKEND"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(backend, "_accel_live", None)
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        backend.resolve_merge_backend(Config(topology=Topology()))


def test_party_meshes_refuses_cached_sub_slices_on_tpu(no_cache_side_effect):
    """A multi-chip sub-slice program loaded back from the persistent
    cache halts a v5e (PR 23's four-chip run): the combination is an
    error up front, not a core halt mid-training."""
    from geomx_tpu.parallel.dp import party_meshes

    class Chip:
        platform = "tpu"

    jax.config.update("jax_compilation_cache_dir", "/some/dir")
    with pytest.raises(RuntimeError, match="persistent compilation cache"):
        party_meshes(2, devices=[Chip() for _ in range(4)])
