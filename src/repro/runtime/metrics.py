"""Step metrics: tokens/s, step-time EMA, and the serving-side KV-tier
counters (``kv_*`` fields)."""
from __future__ import annotations

from typing import Optional


class MetricsLogger:
    def __init__(self, log_fn=print):
        self.log_fn = log_fn
        self.ema: Optional[float] = None
        self.history = []

    def log(self, step: int, loss: float, tokens: int, dt: float, **kw) -> dict:
        self.ema = dt if self.ema is None else 0.9 * self.ema + 0.1 * dt
        tps = tokens / dt if dt > 0 else 0.0
        rec = {"step": step, "loss": float(loss), "tokens_per_s": tps,
               "step_time": dt, "step_time_ema": self.ema, **kw}
        self.history.append(rec)
        self.log_fn(
            f"step {step:5d} | loss {loss:8.4f} | {tps:9.0f} tok/s | "
            f"{dt*1e3:7.1f} ms" + (f" | {k}" if (k := kw.get('note')) else ""))
        return rec


def elastic_step_metrics(*, restarts: int = 0, replans: int = 0,
                         resizes: int = 0, recovery_s: float = 0.0,
                         n_alive: int = 1,
                         membership_version: int = 0) -> dict:
    """Per-step elastic-runtime metric fields (``runtime/elastic.py``).

    All counters are cumulative over the run, not per-step deltas — a step
    record answers "how much recovery has this trajectory absorbed so far":
    ``elastic_restarts`` crash recoveries (checkpoint-restore path),
    ``elastic_replans`` planner invocations (the boot plan counts),
    ``elastic_resizes`` graceful membership changes (live re-shard, no lost
    steps), ``elastic_recovery_s`` cumulative failure->resumed-step wall
    time, ``elastic_n_alive`` / ``elastic_membership_version`` the
    membership view the current incarnation is planned for."""
    return {"elastic_restarts": int(restarts),
            "elastic_replans": int(replans),
            "elastic_resizes": int(resizes),
            "elastic_recovery_s": round(float(recovery_s), 3),
            "elastic_n_alive": int(n_alive),
            "elastic_membership_version": int(membership_version)}


def kv_step_metrics(delta: dict, resident_bytes: int) -> dict:
    """Per-step KV-tier metrics for the serving loop, named like the
    training executor's per-tier counters (``param_in_*`` / ``grad_out_*``).

    ``delta`` is an ``ArrayStore.delta_since(mark)`` dict for the KV store:
    reads are blocks streaming *in* to refill a decode slot (admission),
    writes are sequences parked *out* to the slow tier. ``resident_bytes``
    is the device-resident slot-cache footprint. All values are per-step
    deltas, never cumulative.

    ``kv_in_bytes`` / ``kv_out_bytes`` are *logical* bytes (the decoded
    blocks the cache moved); ``kv_*_wire_bytes`` is what actually crossed
    the tier link — smaller when the store is wrapped in a quantized wire
    format (``core/qformat.py``), identical otherwise."""
    wire_r = int(delta.get("bytes_read", 0))
    wire_w = int(delta.get("bytes_written", 0))
    return {
        "kv_resident_bytes": int(resident_bytes),
        "kv_in_bytes": int(delta.get("logical_bytes_read", wire_r)),
        "kv_out_bytes": int(delta.get("logical_bytes_written", wire_w)),
        "kv_in_wire_bytes": wire_r,
        "kv_out_wire_bytes": wire_w,
        "kv_in_gbps": float(delta.get("read_gbps", 0.0)),
        "kv_out_gbps": float(delta.get("write_gbps", 0.0)),
    }
