"""What the `setup_*` readers share: a read of the program's own registry
(`deeprec_tpu.obs`, what its `/metrics` renders), where the program's recorder
of set-up books every trace, lowering, compile-or-load and cache answer
(deeprec_tpu/obs/compile_log.py). Beside the builders this is the one module
of the benchmark that imports `deeprec_tpu`, and it does so inside the call.

The readers run at the end of the traced run, after the window (in which
nothing compiles: `steady_compiles`) and before the reference runs, so what
the registry holds is set-up's, the harness's own reads for `correct`
included, as `setup_s` holds them too.
"""
from __future__ import annotations

from typing import Optional


def total(name: str, **labels) -> Optional[float]:
    """The counter `name` (as the program registers it: `/metrics` renders
    it with `_total`) summed over the series whose labels hold `labels`, a
    label's value one string or a tuple of them. None where the program has
    no such series (a program from before the recorder), or with the metrics
    plane off (`DEEPREC_OBS=off`); never 0 for what was not counted."""
    try:
        from deeprec_tpu import obs
    except ImportError:
        return None
    if not obs.metrics_enabled():
        return None
    family = obs.default_registry().snapshot()["metrics"].get(name)
    if family is None:
        return None
    want = {k: (v,) if isinstance(v, str) else v for k, v in labels.items()}
    values = [s["value"] for s in family["series"]
              if all(s["labels"].get(k) in v for k, v in want.items())]
    return float(sum(values)) if values else None
