"""Runs of a cell cut to a size a CPU test can hold: the same code path
(harness, engine thread, fused steps, plain reference), two layers of
width 64.  Each run is a subprocess, so the compile-cache settings a run
makes stay out of the test process."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.abspath(os.path.join(HERE, "..", "..", "benchmarks", "chip"))
SRC = os.path.abspath(os.path.join(HERE, "..", "..", "src"))

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 32, "d_ff": 96, "vocab": 256}
ENGINE = {"max_batch": 4, "max_len": 128, "page_size": 16,
          "prefill_slice": 32, "n_pages": None}
CLOSED = {"clients": 4, "greedy_every": 2,
          "prompt": {"dist": "uniform", "lo": 16, "hi": 40},
          "max_new": {"dist": "uniform", "lo": 16, "hi": 40}}

# Faults planted in the timed path (the program, patched in the run's
# process before anything is traced).
FAULTS = {
    # a token altered where it is produced: greedy rows take the runner-up
    "token": (
        "import jax.numpy as jnp\n"
        "from repro.serving import sampler\n"
        "def greedy(logits):\n"
        "    best = jnp.argmax(logits, -1)\n"
        "    masked = logits.at[jnp.arange(logits.shape[0]), best].set(-1e30)\n"
        "    return jnp.argmax(masked, -1).astype(jnp.int32)\n"
        "sampler.greedy = greedy\n"),
    # a step that returns its state unchanged: keys and values never land
    # in the page pools
    "state": (
        "from repro.core.backend import CamformerBackend\n"
        "def _paged_write(self, cache, k, v, positions, page_table, kv_len,"
        " cfg, base=None):\n"
        "    return dict(cache)\n"
        "CamformerBackend._paged_write = _paged_write\n"),
}


def overrides(dtype: str, limit, control: str, n_kv_heads: int) -> dict:
    model = dict(MODEL, dtype=dtype, n_kv_heads=n_kv_heads)
    config = {"model": model, "engine": ENGINE,
              "correct": {"sample_requests": 2, "control": control,
                          "limits": {"mean_logit_gap": limit}}}
    return {"config": config, "traffic": CLOSED}


def run(workload: str, tmp_path, *, limit: float, seed: int = 2**33 + 7,
        dtype: str = "float32", fault: str = "", control: str = "",
        n_kv_heads: int = 2, seconds: float = 3.0) -> dict:
    """One run; with ``control`` (a kind the reference knows) the control
    is read as well.  ``n_kv_heads`` 1 makes the model grouped-query."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {CHIP!r}); sys.path.insert(0, {SRC!r})\n"
        + FAULTS.get(fault, "") +
        "import run\n"
        f"res = run.run_cell({workload!r}, {seed}, {seconds}, False, "
        f"require_chip=False, control={bool(control)}, "
        f"overrides={overrides(dtype, limit, control or 'float8', n_kv_heads)!r})\n"
        "print(json.dumps({k: res[k] for k in ('correct', 'checks', "
        "'_check', 'attempted', 'failed', 'metrics')}))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])
