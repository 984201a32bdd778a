"""What one run recorded, as the per-layer metric readers see it.

A reader (``chipbench/layer_metrics/<metric>.py``) is a module with one
function, ``read(run: RunRecord) -> float | None``.  It returns ``None``
when the run holds nothing for it to read, and the harness then leaves the
metric out of the result line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.trace_reduce import Event, Reduced, kernel_of, leaves
from chipbench.work import (
    Pass,
    attention_calls,
    matmul_calls,
    min_seconds,
    model_flops,
)

LAYER_METRICS_DIR = Path(__file__).resolve().parent / "layer_metrics"

# Device operations of the ABFP matmul kernels (the packed kernel and the
# fused QKV kernel) and of the int8-KV decode attention kernel: custom
# calls named after the jitted functions that launch them.
MATMUL_KERNELS = ("abfp_matmul_packed_pallas", "fused_qkv_packed_pallas")
ATTENTION_KERNELS = ("fused_quantized_decode_attention",)


@dataclasses.dataclass
class RunRecord:
    """Everything a run measured, for the per-layer readers."""

    cfg: dict
    mix: dict
    peaks: dict
    seconds: float
    occupancy: List[float]            # live / capacity at each poll
    traced_passes: List[Pass]         # passes dispatched in traced window
    trace: Optional[Reduced] = None

    def traced_modules(self) -> List[Tuple[Pass, Event]]:
        """(pass, its executable's device event) for each traced pass.

        The engine's decode and prefill executables are the launches named
        after its jitted step closures; they run in dispatch order, and the
        trace holds exactly the passes dispatched inside it.  A count that
        differs means the pairing is wrong, and is an error."""
        if self.trace is None or not self.trace.modules:
            return []
        mods = [e for e in self.trace.modules[0]
                if "_step" in e.name or "_prefill" in e.name]
        if len(mods) != len(self.traced_passes):
            raise ValueError(f"the trace holds {len(mods)} launches of the "
                             f"engine's steps, the harness dispatched "
                             f"{len(self.traced_passes)} passes")
        return list(zip(self.traced_passes, mods))

    def kernel_events(self, names: Sequence[str]) -> List[Event]:
        """Device operations of device 0 that are one of the kernels
        ``names``, inside the traced window."""
        if self.trace is None or not self.trace.ops:
            return []
        a, b = self.trace.window
        return [e for e in leaves(self.trace.ops[0])
                if a <= e.start and e.end <= b and kernel_of(e.name) in names]

    # -- helpers the readers share -----------------------------------------

    def mean_pass_ms(self, kind: str, bucket: Optional[int] = None
                     ) -> Optional[float]:
        """Mean device time, in ms, of the traced passes of one kind (and
        prefill bucket, where given)."""
        times = [e.dur for p, e in self.traced_modules()
                 if p.kind == kind and bucket in (None, p.bucket)]
        return sum(times) / len(times) / 1e6 if times else None

    def kv_copy_ms(self) -> Optional[float]:
        """Mean device time, in ms, per traced decode pass, of the
        operations other than the attention kernel whose output has both a
        cache-length axis (``max_len``) and a key/value-head axis: the
        copies, slices and update-slices that move the K/V cache and its
        scales around the kernel."""
        decode = [e for p, e in self.traced_modules() if p.kind == "decode"]
        if not decode:
            return None
        axes = {self.cfg["engine"]["max_len"],
                self.cfg["num_key_value_heads"]}
        total = 0
        for e in leaves(self.trace.ops[0]):
            if kernel_of(e.name) in ATTENTION_KERNELS:
                continue
            if not axes <= set(shape_of(self.trace.types.get(e.name, ""))):
                continue
            if any(m.start <= e.start and e.end <= m.end for m in decode):
                total += e.dur
        return total / len(decode) / 1e6

    def pass_mfu(self, kinds: Sequence[str]) -> Optional[float]:
        """Model FLOPs of the real tokens of the traced passes of these
        kinds over their device time, as a percentage of the int8 peak."""
        pairs = [(p, e) for p, e in self.traced_modules() if p.kind in kinds]
        secs = sum(e.dur for _, e in pairs) / 1e9
        if not pairs or secs <= 0:
            return None
        flops = sum(model_flops(p, self.cfg) for p, _ in pairs)
        return 100.0 * flops / secs / self.peaks["int8_ops_per_s"]

    def window_mfu(self) -> Optional[float]:
        """Model FLOPs of every real token of the traced window over the
        window's length, as a percentage of the int8 peak."""
        pairs = self.traced_modules()
        if not pairs:
            return None
        flops = sum(model_flops(p, self.cfg) for p, _ in pairs)
        return 100.0 * flops / self.trace.window_s \
            / self.peaks["int8_ops_per_s"]

    def roofline(self, kernels: Sequence[str], calls_of) -> Optional[float]:
        """Least time the chip needs for the work ``calls_of(pass, cfg)``
        of the traced passes, over the device time of the named kernels,
        as a percentage."""
        pairs = self.traced_modules()
        events = self.kernel_events(kernels)
        secs = sum(e.dur for e in events) / 1e9
        if not pairs or not events or secs <= 0:
            return None
        need = sum(min_seconds(calls_of(p, self.cfg),
                               self.peaks["int8_ops_per_s"],
                               self.peaks["hbm_bytes_per_s"])
                   for p, _ in pairs)
        return 100.0 * need / secs if need > 0 else None

    def matmul_roofline(self) -> Optional[float]:
        """Roofline share of the ABFP matmul kernels."""
        return self.roofline(MATMUL_KERNELS, matmul_calls)

    def attention_roofline(self) -> Optional[float]:
        """Roofline share of the int8-KV decode attention kernel."""
        return self.roofline(ATTENTION_KERNELS, attention_calls)

    def idle_share(self) -> Optional[float]:
        """Percentage of the traced window with no operation on the
        device."""
        if self.trace is None or not self.trace.ops:
            return None
        return 100.0 * (1.0 - self.trace.busy_s() / self.trace.window_s)


def shape_of(type_text: str) -> Tuple[int, ...]:
    """``s8[1,32,2048,5,64]`` -> (1, 32, 2048, 5, 64); () for no shape."""
    if "[" not in type_text or not type_text.endswith("]"):
        return ()
    dims = type_text[type_text.index("[") + 1:-1]
    return tuple(int(d) for d in dims.split(",") if d.isdigit())


def quantile(xs: Sequence[float], q: float) -> Optional[float]:
    """The q-quantile (0..1, linear interpolation) or None when empty."""
    if not len(xs):
        return None
    return float(np.quantile(np.asarray(xs, np.float64), q))


def load_reader(metric: str):
    """The ``read`` function of ``chipbench/layer_metrics/<metric>.py``."""
    path = LAYER_METRICS_DIR / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {metric!r} "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_layer_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(run: RunRecord, metrics: Sequence[dict]) -> Dict[str, dict]:
    """Every per-layer metric that has something to read in this run."""
    out = {}
    for m in metrics:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
