"""Steers a benchmark run onto the CPU at smoke width, from a test."""
import json
import sys

# a width the CPU runs in seconds: every cut dimension still a multiple of
# the 128-wide pruning tile
SMOKE = dict(hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
             intermediate_size=256, embedding_size=32, vocab_size=512,
             max_position_embeddings=128)


def patch(setattr_, run, cache_dir) -> None:
    """Replace the look for a chip, the peaks table, the compile cache and
    the configuration's widths; ``setattr_`` is ``monkeypatch.setattr`` or
    ``setattr``."""
    import jax

    load_cell = run.load_cell

    def small_cell(name):
        bench, cell, cfg, mix = load_cell(name)
        cfg.update(SMOKE)
        if cfg.get("threshold_profile"):
            cfg["threshold_profile"] = dict(cfg["threshold_profile"], requests=32)
        return bench, cell, cfg, mix

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
