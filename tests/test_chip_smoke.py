"""``chip_smoke.py``: its workload at a tiny size on the CPU (Pallas in
interpret mode) against the dict oracle, and its refusal to report a
result off a TPU."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

# Store sizes cut so that 4096 1 KB records still flush, form grouped L0
# and two disk levels (the default store needs ~1M records for that).
TINY = dict(total_memory_bytes=4 << 20, write_memory_bytes=256 << 10,
            sim_cache_bytes=256 << 10, active_sstable_bytes=64 << 10,
            sstable_bytes=64 << 10)


def test_smoke_workload_matches_oracle_in_interpret_mode():
    out = chip_smoke.run(4096, log=lambda *a: None, **TINY)
    assert out["records"] == 4096
    assert out["disk_levels"] >= 2
    assert out["fallback_calls"] == 0
    assert out["fused_launches"] > 0 and out["pool_store_hits"] > 0
    assert out["parity_tables"] >= 2


def test_smoke_refuses_a_cpu():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert '"ok"' not in p.stdout

