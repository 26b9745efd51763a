"""Small stand-ins for the benchmark's configurations, for CPU tests: the
configuration file's model at smoke widths, with the program's own smoke
preset resized to match."""
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as RUN  # noqa: E402

# Cells whose files are under bench/ but which wait for their readings on
# the chip (the knee, the program's and the control's gaps) before they
# enter BENCHMARK.json.  The tests rehearse them with the others.
QUEUED = {
    "configs": [{"name": "glm4-9b", "file": "bench/configs/glm4-9b.json"}],
    "workloads": [{"name": "glm4-9b.tp2x2-chat", "config": "glm4-9b",
                   "traffic": "tp2x2-chat", "chips": 4}],
}


def benchmark():
    """BENCHMARK.json with the queued cells and configurations beside its
    own."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for key, queued in QUEUED.items():
        have = {x["name"] for x in bench[key]}
        bench[key] = bench[key] + [x for x in queued if x["name"] not in have]
    return bench


def smoke(workload, d_model=None, n_layers=None, vocab=None, dtype="bfloat16"):
    """The cell's spec with its configuration cut to smoke size, and the
    program model that matches it."""
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    spec = copy.deepcopy(RUN.cell_of(benchmark(), workload))
    cfg = spec["cfg"]
    pc = RUN.program_model(cfg, get_smoke_config(cfg["program_model"])
                           ).with_(dtype=jnp.dtype(dtype))
    if d_model:
        pc = pc.with_(d_model=d_model, d_ff=4 * d_model)
    if n_layers:
        pc = pc.with_(n_layers=n_layers)
    if vocab:
        pc = pc.with_(vocab_size=vocab)
    cfg["config"].update(
        hidden_size=pc.d_model, intermediate_size=pc.d_ff,
        num_hidden_layers=pc.n_layers, num_attention_heads=pc.n_heads,
        num_key_value_heads=pc.n_kv_heads, vocab_size=pc.vocab_size,
        head_dim=pc.d_head, torch_dtype=dtype)
    cfg["engine"].update(max_len=256, max_seqs=4, n_blocks=80, chunk_blocks=2)
    tr = spec["traffic"]
    tr["output"].update(min=4, median=16, max=48)
    if "prompt" in tr:
        tr["prompt"].update(min=4, median=40, max=160)
    else:
        tr["system_prompts"].update(count=3, tokens=48)
        tr.update(max_prompt=160, turn_gap_s=0.5)
        for k in ("user", "assistant"):
            tr[k].update(min=4, max=24)
    if "clients" in tr:
        tr["clients"] = 4
    arr = tr.get("arrivals", {})
    for k in ("rate_rps", "mean_rps"):
        if k in arr:
            arr[k] = 3.0
    return spec, pc


def read_json(path):
    with open(path) as f:
        return json.load(f)


def trace_fixture():
    """``fixtures/trace_small.pbtxt``, a hand-made TPU-like profile, as
    ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData
    with open(os.path.join(BENCH, "tests", "fixtures",
                           "trace_small.pbtxt")) as f:
        text = "".join(l for l in f if not l.lstrip().startswith("#"))
    return ProfileData.from_text_proto(text)


class CopyingUploads:
    """``jax.numpy`` for the engine module, except that ``asarray`` copies
    a numpy array first.  On the CPU backend ``jnp.asarray`` of a numpy
    slice aliases host memory, and the engine uploads slices of host
    mirrors (block tables, lengths, next tokens) that it later changes in
    place, so a CPU run under load serves tokens computed from state the
    host already moved on.  A TPU upload copies; this gives CPU rehearsals
    the chip's semantics."""

    def __init__(self, jnp):
        self._jnp = jnp

    def __getattr__(self, name):
        return getattr(self._jnp, name)

    def asarray(self, a, *args, **kwargs):
        import numpy as np
        if isinstance(a, np.ndarray):
            a = a.copy()
        return self._jnp.asarray(a, *args, **kwargs)


def copy_uploads(monkeypatch):
    from repro.serving import engine
    monkeypatch.setattr(engine, "jnp", CopyingUploads(engine.jnp))
