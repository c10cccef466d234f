"""Rate at which a chip receives what the tensor-parallel step gathers:
the bits the traced chunks' all-gathers bring to one chip (``bytes_fn`` of
``harness/gather_bytes.py``, at the widths of the configuration the
metric's file names) over the device self time of the operations matching
``patterns`` in the trace, in Gbit/s. A trace without such operations (one
chip, or a program that gathers nothing) gives nothing."""

from benchmarks.harness import cluster, gather_bytes, spec as specs


def read(obs, spec):
    if obs.trace is None or not obs.chunks:
        return None
    secs = obs.trace.op_seconds(spec["patterns"])
    if secs <= 0:
        return None
    hf = specs._load(specs.BENCH_DIR / "configs" / f"{spec['config']}.json")
    sizes = gather_bytes.sizes_from_config(
        hf, cluster.ml_config(hf.get("deployment", {})))
    need = gather_bytes.FUNCTIONS[spec["bytes_fn"]](obs.chunks, sizes)
    return need * 8.0 / 1e9 / secs if need > 0 else None
