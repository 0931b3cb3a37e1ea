"""Steers a benchmark run onto the CPU at smoke width, from a test."""
import json
import sys


def shrink(cfg: dict) -> dict:
    """``cfg`` at the smoke width of its driver (the driver's ``SMOKE``); a
    dict there updates the group of that name where ``cfg`` has one."""
    from chipbench import run

    for k, v in run.load_driver(cfg).SMOKE.items():
        if not isinstance(v, dict):
            cfg[k] = v
        elif cfg.get(k):
            cfg[k] = {**cfg[k], **v}
    return cfg


def patch(setattr_, run, cache_dir) -> None:
    """Replace the look for a chip, the peaks table, the compile cache and
    the configuration's widths; ``setattr_`` is ``monkeypatch.setattr`` or
    ``setattr``."""
    import jax

    load_cell = run.load_cell

    def small_cell(name):
        bench, cell, cfg, mix = load_cell(name)
        return bench, cell, shrink(cfg), mix

    setattr_(run, "load_cell", small_cell)
    setattr_(run, "find_devices", lambda chips: jax.devices()[:chips])
    setattr_(run, "peaks_for", lambda kind: {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    setattr_(run, "CACHE_DIR", cache_dir)


def last_line(text: str):
    lines = text.strip().splitlines()
    return json.loads(lines[-1]) if lines else None


if __name__ == "__main__":      # python smoke.py <cache dir> <run.py arguments...>
    from pathlib import Path

    from chipbench import run

    patch(setattr, run, Path(sys.argv[1]))
    sys.exit(run.main(sys.argv[2:]))
