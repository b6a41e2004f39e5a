#!/usr/bin/env bash
# Pallas fused-probe smoke: the hand-fused probe kernels
# (engine/pallas.py) end-to-end on a small world, CI-runnable in Pallas
# INTERPRET mode (JAX_PLATFORMS=cpu).  Asserts (1) bitwise parity
# pallas-vs-XLA through the throughput batch path (caveats, wildcards,
# usersets, expirations), the pinned latency path (incl. the zero-
# retrace contract on warm same-tier dispatches), and the packed-uint16
# + aligned-ladder layouts; (2) the perf ledger's one-pass bytes bar:
# pallas_bytes_model must show a per-table bytes-accessed reduction and
# prepare must publish vmem_resident_bytes > 0.  Interpret-mode honesty:
# rates printed here are correctness-only — the bytes win is a model,
# not measured on a device.  Prints
# PALLAS-SMOKE-OK on success and one JSON metric line for
# benchmarks/run_all.py (config 25).
set -euo pipefail
cd "$(dirname "$0")/.."

JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} python - <<'EOF'
import dataclasses
import datetime as dt
import json
import random
import sys
import time

import numpy as np

from gochugaru_tpu.utils.platform import force_cpu_platform

force_cpu_platform(8)

sys.path.insert(0, ".")
from gochugaru_tpu import rel
from gochugaru_tpu.engine import pallas as P
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils import perf as _perf
from gochugaru_tpu.utils.metrics import default as _m

t0 = time.time()
NOW = 1_700_000_000_000_000

assert P.available(), "jaxlib must ship jax.experimental.pallas here"
assert P.interpret_mode(), "smoke runs the kernels through the interpreter"

SCHEMA = """
caveat on_tuesday(day string) { day == "tuesday" }
definition user {}
definition team {
    relation member: user | team#member | user:*
    permission everyone = member
}
definition doc {
    relation reader: user | user:* | team#member | team#everyone
    relation writer: user | team#member
    permission edit = writer
    permission view = reader + edit
}
"""

rng = random.Random(13)
rels = []
for t in range(1, 24):
    rels.append(rel.must_from_tuple(
        f"team:t{t - 1 if t % 5 else rng.randrange(t)}#member",
        f"team:t{t}#member"))
for t in range(24):
    rels.append(rel.must_from_tuple(
        f"team:t{t}#member", f"user:u{rng.randrange(12)}"))
rels.append(rel.must_from_tuple("team:t3#member", "user:*"))
for _ in range(220):
    d, u = f"doc:d{rng.randrange(24)}", f"user:u{rng.randrange(12)}"
    k = rng.random()
    if k < 0.08:
        r = rel.must_from_tuple(f"{d}#reader",
                                f"team:t{rng.randrange(24)}#member")
    elif k < 0.11:
        r = rel.must_from_tuple(f"{d}#reader", "user:*")
    else:
        r = rel.must_from_triple(
            d, "reader" if rng.random() < 0.8 else "writer", u)
    if rng.random() < 0.12:
        r = r.with_caveat("on_tuesday",
                          {"day": "tuesday"} if rng.random() < 0.5 else {})
    if rng.random() < 0.07:
        r = dataclasses.replace(r, expiration=dt.datetime.fromtimestamp(
            (NOW + rng.randrange(-10**9, 10**12)) / 1e6, tz=dt.timezone.utc))
    rels.append(r)

cs = compile_schema(parse_schema(SCHEMA))
snap = build_snapshot(1, cs, Interner(), rels, epoch_us=NOW)
checks = [
    rel.must_from_triple(f"doc:d{rng.randrange(24)}",
                         rng.choice(["view", "edit"]),
                         f"user:u{rng.randrange(12)}")
    for _ in range(48)
]
checks = [q.with_caveat("", {"day": rng.choice(["tuesday", "friday"])})
          if rng.random() < 0.4 else q for q in checks]

# (1) throughput batch path + packed/aligned layouts: bitwise parity
n_verdicts = 0
for cfg in ({}, {"flat_packed": True},
            {"flat_packed": True, "flat_aligned": True}):
    ex = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=False, **cfg))
    ep = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=True, **cfg))
    rx = ex.check_batch(ex.prepare(snap), checks, now_us=NOW)
    rp = ep.check_batch(ep.prepare(snap), checks, now_us=NOW)
    for a, b in zip(rx, rp):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"parity broke under {cfg or 'default layout'}"
    n_verdicts += len(checks)
print(f"batch parity: ok ({n_verdicts} verdicts bitwise, 3 layouts)",
      file=sys.stderr)

# (2) pinned latency path: parity + ZERO retraces on warm dispatches
ep = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=True))
ex = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=False))
dp, dx = ep.prepare(snap), ex.prepare(snap)
lp = ep.latency_path(dp)
interner = snap.interner
slot = cs.slot_of_name
B = 16
q_res = np.array([interner.node("doc", f"d{i % 24}") for i in range(B)],
                 np.int32)
q_perm = np.full(B, slot["view"], np.int32)
q_subj = np.array([interner.node("user", f"u{i % 12}") for i in range(B)],
                  np.int32)
assert lp.dispatch_columns(q_res, q_perm, q_subj, now_us=NOW) is not None
warm = lp.compile_count
for i in range(1, 5):
    got = lp.dispatch_columns(np.roll(q_res, i), q_perm,
                              np.roll(q_subj, i), now_us=NOW)
    ref = ex.check_columns(dx, np.roll(q_res, i), q_perm,
                           np.roll(q_subj, i), now_us=NOW)
    for a, b in zip(got, ref):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "latency-path parity broke"
assert lp.compile_count == warm, "warm pallas dispatch retraced"
print(f"latency parity: ok (4 warm dispatches, {warm} compiles, 0 retraces)",
      file=sys.stderr)

# (3) the ledger bytes bar: the one-pass model must show a per-table
# reduction, and prepare must have pinned the VMEM-resident plan
epk = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=True,
                                               flat_packed=True))
dpk = epk.prepare(snap)
model = _perf.pallas_bytes_model(dpk)
assert model, "byte model empty"
saved = sum(row["saved"] for row in model.values())
xla = sum(row["xla"] for row in model.values())
assert saved > 0, "fused kernels must model a bytes reduction"
vmem = _m.gauge("perf.vmem_resident_bytes")
assert vmem > 0, "prepare must publish the VMEM residency plan"
frac = saved / max(xla, 1)
print(f"bytes bar: ok ({saved} B/check modeled saved, "
      f"{100 * frac:.0f}% of the XLA pass; vmem_resident={int(vmem)} B)",
      file=sys.stderr)

print(json.dumps({
    "metric": "pallas_smoke_bytes_saved_frac", "value": round(frac, 4),
    "unit": "fraction of XLA bytes/check", "vs_baseline": 1.0,
    "edges": int(snap.num_edges), "batch": len(checks),
    "vmem_resident_bytes": int(vmem),
    "wall_s": round(time.time() - t0, 1),
}))
EOF

echo "PALLAS-SMOKE-OK"
