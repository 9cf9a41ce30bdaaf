"""CPU tests of the benchmark.  They run apart from the repository's
tier-1 suite (``python -m pytest benchmarks/chip/tests``), on the CPU,
with test-only cells at CPU size; a run's look for a chip is replaced by
the CPU devices and the v5e row of the peaks table."""
import contextlib
import io
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

REPO = Path(__file__).resolve().parents[3]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(REPO / "src"), str(REPO)]

LLM_NUMBERS = ("train_loss", "teacher", "adapter_change", "first_moment",
               "first_moment_median")
ROUNDS_NUMBERS = ("server_loss_r1", "client_loss_r1", "theta_change_r1",
                  "evals_differ_r1")
# test-only cells: (cell, config file, traffic file, chips, numbers)
TEST_CELLS = [
    ("tiny.finetune", "tiny", "tiny-finetune", 1, LLM_NUMBERS),
    ("tiny.finetune.mesh4", "tiny", "tiny-finetune", 4, LLM_NUMBERS),
    ("tiny.rounds", "tiny", "tiny-rounds", 1, ROUNDS_NUMBERS),
]
LOOSE = 1e9


def add_cell(root: Path, cell: str, config: str, traffic: str, chips: int,
             limits: dict) -> None:
    """Adds a cell the way a later change would: new files and one
    ``workloads`` entry (plus its configuration's entry)."""
    here = root / "benchmarks" / "chip"
    cfg_file = here / "configs" / f"{config}.json"
    if not cfg_file.exists():
        shutil.copy(DATA / f"{config}.json", cfg_file)
    tfile = here / "traffic" / f"{traffic}.json"
    if not tfile.exists():
        shutil.copy(DATA / f"{traffic}.json", tfile)
    (here / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if config not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append({
            "name": config, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/chip/configs/{config}.json"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": chips,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any(w.split(".", 1)[1] == cell.split(".", 1)[1]
                                    for w in m["workloads"] if "." in w):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture
def checkout(tmp_path):
    """A checkout in a temporary directory: the program (linked), a copy
    of the benchmark's directory and of BENCHMARK.json, and the test
    cells added as new files."""
    (tmp_path / "src").symlink_to(REPO / "src")
    shutil.copytree(REPO / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for cell, config, traffic, chips, numbers in TEST_CELLS:
        add_cell(tmp_path, cell, config, traffic, chips,
                 {k: LOOSE for k in numbers})
    return tmp_path


@pytest.fixture
def on_cpu(monkeypatch):
    """Skips the look for a chip: the CPU devices stand in, with the v5e
    row of the peaks table."""
    import jax
    from benchmarks.chip import harness
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    peaks = harness.peaks_for("TPU v5 lite")
    monkeypatch.setattr(harness, "peaks_for", lambda kind: peaks)


def run_cell(root: Path, workload: str, *, seed: int = 2 ** 31 + 12345,
             seconds: float = 1.0, trace: int = 0) -> dict:
    """One run of ``run.py``'s main in this process; its last line."""
    from benchmarks.chip import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
