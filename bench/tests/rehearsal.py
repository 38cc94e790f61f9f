"""Tiny cells for CPU rehearsals of whole runs (no TPU look; the timed
path is the program, or the plain reference -- control or fault -- put in
the program's place)."""
import io
import time

from bench.lib import cells, harness

SEED = 4_294_967_311   # wider than 32 bits, as run seeds may be


def tiny(name: str, chips: int = 1) -> dict:
    cell = cells.load_cell(name)
    if cell["traffic"]["path"] == "sim":
        cell["config"] = dict(cell["config"], rows=700, num_honest=10)
        cell["traffic"] = dict(cell["traffic"],
                               byzantine=min(cell["traffic"]["byzantine"], 4))
    else:
        cell["config"] = dict(cell["config"], n_layer=1, d_model=128,
                              vocab_size=256, d_state=16, headdim=32,
                              chunk_size=16)
        cell["traffic"] = dict(cell["traffic"], seq=16, per_worker_batch=2,
                               mesh=[chips, 1])
        cell["chips"] = chips
    return cell


def run(cell, impl):
    return harness.run(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                       require_tpu=False, impl=impl, log=io.StringIO())
