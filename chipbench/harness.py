"""What every runner shares: the run's context, the compile counter, the
profiler window and the program's model built with seeded weights."""

from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Optional


@dataclasses.dataclass
class Context:
    config: dict            # the configuration file, rehearsal laid over
    traffic: dict           # the traffic file, rehearsal laid over
    seed: int
    rehearse: bool
    cache_dir: str          # fixed, inside the checkout
    trace_dir: Optional[str]
    t_start: float          # perf_counter at process start
    compiles: "CompileLog"
    peaks: Optional[dict]   # None in a rehearsal: no share of a peak then
    marks: list = dataclasses.field(default_factory=list)

    def mark(self, name: str) -> None:
        """Seconds since process start at the end of a set-up phase."""
        self.marks.append([name, time.perf_counter() - self.t_start])

    @property
    def model(self) -> dict:
        return self.config["model"]

    def family(self, kind: str):
        return family_module(self.config, kind)


def family_module(config: dict, kind: str):
    """``chipbench.flops.<family>`` or ``chipbench.references.<family>``."""
    return importlib.import_module(f"chipbench.{kind}.{config['family']}")


class CompileLog:
    """Executables the process asked XLA for, from ``jax.monitoring``.
    jax 0.9 fires ``backend_compile_duration`` for a persistent-cache hit
    too, so requests and hits are counted apart (as ``chip_smoke.py``
    does). "No compile in the window" means no request."""

    def __init__(self):
        from jax import monitoring

        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += float(duration)
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.cache_hits,
                "seconds": self.seconds}


def sleep_through_window(trace_dir: Optional[str], t0: float, t1: float,
                         after: float, seconds: float):
    """Sleep from now to ``t1``. With a ``trace_dir``, trace ``seconds`` of
    the window from ``after`` seconds into it. Returns the host-clock
    interval that was traced, or None."""
    import jax

    traced = None
    if trace_dir:
        _sleep_until(t0 + after)
        # device and runtime events; no Python call tracing, which slows
        # the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        a = time.perf_counter()
        _sleep_until(min(t1, a + seconds))
        traced = (a, time.perf_counter())
        jax.profiler.stop_trace()
    _sleep_until(t1)
    return traced


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(min(d, 0.25))


def leaf_targets(config: dict) -> dict:
    """The reference's leaf name -> the program's parameter name, from the
    configuration's ``zoo.param_names``."""
    names = config["zoo"]["param_names"]
    per_layer = family_module(config, "references").LAYER_LEAVES
    out = {k: v for k, v in names.items()
           if k != "layer" and k not in per_layer}
    for i in range(int(config["model"]["n_layer"])):
        prefix = names["layer"].format(i=i)
        out.update({f"h{i}.{k}": prefix + names[k] for k in per_layer})
    return out


def build_zoo_model(ctx: Context, trainable: bool):
    """The program's model with the reference's seeded weights set into
    it by name (the configuration's ``zoo.param_names`` maps the
    reference's leaf names onto the program's), in the configured type."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import model_zoo

    zoo = ctx.config["zoo"]
    dtype = ctx.config["dtype"]
    ctx.mark("imports")
    net = getattr(model_zoo, zoo["factory"])(zoo["spec"], **zoo["args"])
    net.cast(dtype)
    if not trainable:
        net.collect_params().setattr("grad_req", "null")
    ref = ctx.family("references")
    g, layers = ref.draw_all(ctx.model, ctx.seed, dtype)
    targets = leaf_targets(ctx.config)
    params = net._collect_params_with_prefix()
    todo = set(params)
    for leaf, arr in ref.flatten_leaves(g, layers).items():
        params[targets[leaf]].set_data(mx.nd.NDArray(arr))
        todo.discard(targets[leaf])
    ctx.mark("weights")
    if todo:
        raise RuntimeError(f"parameters left without seeded weights: "
                           f"{sorted(todo)[:5]}")
    return net


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0
