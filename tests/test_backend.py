"""How a process meets its backend: nothing on the main path may hide the
device.  ``chip_smoke.py`` refuses to run off the chip, ``dev=`` must name
the backend the process is on, the compile cache has one placement rule,
the MFU denominator never guesses, and the bench scripts do not rerun on
the CPU.  (The chip side of all this is ``python chip_smoke.py`` through
the chip tool; these are the CPU-side guarantees.)"""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from cxxnet_tpu.nnet import trainer as trainer_mod
from cxxnet_tpu.nnet.trainer import DeviceConfigError, select_devices
from cxxnet_tpu.obs.programs import UnknownDeviceKindError, peak_flops
from cxxnet_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MLP = """
netconfig=start
layer[+1] = fullc:fc1
  nhidden = 4
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
batch_size = 4
"""


def _env(**extra):
    """The parent's environment without the test pins."""
    env = {k: v for k, v in os.environ.items()
           if k not in ('JAX_PLATFORMS', 'XLA_FLAGS',
                        'JAX_COMPILATION_CACHE_DIR', 'CXXNET_PEAK_TFLOPS')}
    env.update(extra)
    return env


def _run(argv, env, cwd=REPO, timeout=300):
    return subprocess.run([sys.executable] + argv, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


# --- chip_smoke.py ----------------------------------------------------------

@pytest.mark.parametrize('pin', [{'JAX_PLATFORMS': 'cpu'}, {}],
                         ids=['pinned-cpu', 'unpinned'])
def test_chip_smoke_refuses_to_run_off_the_chip(pin):
    r = _run(['chip_smoke.py'], _env(**pin), timeout=120)
    if not pin and r.returncode == 0:
        assert '"platform": "tpu"' in r.stdout.splitlines()[-1]
        pytest.skip('this machine has a chip: the smoke ran and passed')
    assert r.returncode != 0
    assert "backend is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_phases_dry_run_on_cpu():
    """Both phases of the smoke and their checks, on the CPU at batch 8:
    how a change to the smoke (or to the path it drives) is debugged
    before chip time is spent on it."""
    code = ('import chip_smoke as cs; ph = cs.Phases(); '
            'cs.drive(ph, batch=8); '
            'print("PHASES", [r[0] for r in ph.rows])')
    r = _run(['-c', code],
             _env(JAX_PLATFORMS='cpu', CXXNET_PEAK_TFLOPS='0.5'),
             timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PHASES ['build+data', 'train', 'pred']" in r.stdout
    assert '4 optimizer steps at batch 8' in r.stdout


def test_package_import_and_packer_touch_no_backend(tmp_path):
    """One process per chip: importing the package, and the one child the
    smoke and the bench scripts start, must not even import jax."""
    r = _run(['-c', 'import sys, cxxnet_tpu; assert "jax" not in sys.modules'],
             _env(PYTHONPATH=REPO))
    assert r.returncode == 0, r.stderr
    (tmp_path / 'a.lst').write_text('')
    packer = os.path.join(REPO, 'tools', 'im2bin.py')
    code = ('import runpy, sys\n'
            'sys.argv = ["im2bin.py", "a.lst", ".", "a.bin"]\n'
            f'try:\n    runpy.run_path({packer!r}, run_name="__main__")\n'
            'except SystemExit as e:\n    assert not e.code\n'
            'assert "jax" not in sys.modules')
    r = _run(['-c', code], _env(), cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr


# --- the compile cache ------------------------------------------------------

def test_compile_cache_env_set_means_nothing_is_set_in_code(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/somewhere/else')
    assert backend.enable_compile_cache() == '/somewhere/else'
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_the_checkout_from_any_cwd(tmp_path):
    code = ('import jax; from cxxnet_tpu.utils.backend import '
            'enable_compile_cache as e; '
            'print(e()); print(jax.config.jax_compilation_cache_dir)')
    env = _env(JAX_PLATFORMS='cpu', PYTHONPATH=REPO)
    outs = [_run(['-c', code], env, cwd=cwd).stdout.split()
            for cwd in (REPO, str(tmp_path))]
    want = os.path.join(REPO, '.jax_cache')
    assert outs == [[want, want], [want, want]]


# --- dev= -------------------------------------------------------------------

def test_dev_runs_any_kind_on_the_cpu_when_pinned():
    devs = jax.devices()
    assert backend.cpu_pinned()
    assert select_devices('tpu', devs) == [devs[0]]
    assert select_devices('', devs) == [devs[0]]
    assert select_devices('tpu:0-3', devs) == devs[:4]
    # ordinals wrap (and de-dup) under the pin: the example confs'
    # tpu:0-3 still drive a one-device CPU process
    assert select_devices('gpu:0-99', devs) == devs
    assert select_devices('tpu:0-3', devs[:1]) == devs[:1]


def test_dev_kind_and_ordinals_are_checked_when_unpinned(monkeypatch):
    monkeypatch.setattr(trainer_mod, 'cpu_pinned', lambda: False)
    devs = jax.devices()
    with pytest.raises(DeviceConfigError, match="'cpu' backend"):
        select_devices('tpu', devs)
    with pytest.raises(DeviceConfigError, match="'cpu' backend"):
        select_devices('tpu:0-3', devs)
    with pytest.raises(DeviceConfigError, match='out of range'):
        select_devices(f'cpu:0-{len(devs)}', devs)
    assert select_devices('cpu', devs) == [devs[0]]
    assert select_devices('cpu:1,3', devs) == [devs[1], devs[3]]


def test_cli_rejects_a_dev_the_backend_is_not(tmp_path):
    """Unpinned, the CLI exits non-zero with the typed error before it
    touches data.  ``gpu`` keeps the test true on a chip machine too; the
    pinned direction is every other CLI test in the suite."""
    conf = tmp_path / 'mlp.conf'
    conf.write_text(_MLP + 'dev = gpu\n')
    r = _run(['-m', 'cxxnet_tpu.main', str(conf)], _env(), timeout=120)
    assert r.returncode != 0
    assert 'DeviceConfigError' in r.stderr and "'gpu'" in r.stderr


# --- MFU denominator, task names, bench backend check -----------------------

def test_peak_flops_raises_on_an_unknown_accelerator(monkeypatch):
    monkeypatch.delenv('CXXNET_PEAK_TFLOPS', raising=False)
    v5e = types.SimpleNamespace(platform='tpu', device_kind='TPU v5 lite')
    assert peak_flops(v5e) == 197e12
    odd = types.SimpleNamespace(platform='tpu', device_kind='TPU v9 odd')
    with pytest.raises(UnknownDeviceKindError, match='TPU v9 odd'):
        peak_flops(odd)
    monkeypatch.setenv('CXXNET_PEAK_TFLOPS', '100')
    assert peak_flops(odd) == 100e12


def test_unknown_task_raises(tmp_path):
    from cxxnet_tpu.main import LearnTask
    conf = tmp_path / 'mlp.conf'
    conf.write_text(_MLP)
    with pytest.raises(ValueError, match="unknown task 'traim'"):
        LearnTask().run([str(conf), 'task=traim'])


def test_bench_chip_mode_without_a_chip_is_an_error_not_a_cpu_rerun(
        monkeypatch, capsys):
    sys.path.insert(0, REPO)
    import bench
    assert backend.require_chip() == 'cpu'          # pinned: correctness run
    monkeypatch.setattr(backend, 'cpu_pinned', lambda: False)
    with pytest.raises(backend.BackendUnavailable, match="'cpu'"):
        backend.require_chip()
    monkeypatch.setattr(sys, 'argv', ['bench.py', 'alexnet'])
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out['value'] is None
    assert out['error'].startswith('BackendUnavailable')


# --- native runtime build ---------------------------------------------------

def test_failed_native_build_says_so_once(tmp_path, monkeypatch, capsys):
    from cxxnet_tpu.runtime import native
    (tmp_path / 'Makefile').write_text(
        "all:\n\t@echo 'x.cc:1: error: no such compiler' >&2; exit 1\n")
    monkeypatch.setattr(native, '_lib_path',
                        lambda: str(tmp_path / 'libcxxnet_runtime.so'))
    monkeypatch.setattr(native, '_TRIED', False)
    monkeypatch.setattr(native, '_LIB', None)
    monkeypatch.delenv('CXXNET_NO_NATIVE', raising=False)
    assert native._load() is None
    err = capsys.readouterr().err
    assert 'error: no such compiler' in err and 'pure-Python' in err
    assert str(tmp_path) in err
    assert native._load() is None                    # tried once
    assert capsys.readouterr().err == ''
