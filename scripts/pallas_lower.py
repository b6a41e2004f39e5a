#!/usr/bin/env python3
"""Lower every Pallas fused-probe kernel call with the device compiler,
one call at a time, and print the verdict of each.

``engine/pallas.py`` is tested in interpret mode on the CPU; whether
Mosaic accepts a kernel is only known by compiling it on a TPU.  A chip
call should not spend its minutes on a host prepare, so this runs in two
steps:

    # anywhere (CPU is fine): record the geometry of every fused-probe
    # call the real programs make on the chip_smoke world
    python scripts/pallas_lower.py --dump-geometry geom.json [--tiny]

    # on the chip: compile each recorded call at exactly those shapes
    python scripts/pallas_lower.py --geometry geom.json

Step 1 traces the tier-4096 check program and the lookup run probes
abstractly (``jax.eval_shape``) under ``EngineConfig(pallas=True)``, once
with bucket-aligned tables (the TPU default) and once without, and
records each ``fused_probe`` / ``fused_probe_aligned`` call.  Step 2
prints one JSON line per distinct call: ``ok`` or the compiler's message.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _shape(a):
    return {"shape": list(a.shape), "dtype": str(a.dtype)}


def _jsonable(x):
    if isinstance(x, (tuple, list)):
        return [_jsonable(v) for v in x]
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if hasattr(x, "item"):
        return x.item()
    return x


def _tupled(x):
    return tuple(_tupled(v) for v in x) if isinstance(x, list) else x


def dump_geometry(path: str, tiny: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import chip_smoke
    from gochugaru_tpu import consistency, new_tpu_evaluator
    from gochugaru_tpu.engine import pallas as P
    from gochugaru_tpu.engine import spmv
    from gochugaru_tpu.engine.device import DeviceEngine
    from gochugaru_tpu.engine.plan import EngineConfig

    size = chip_smoke.TINY if tiny else chip_smoke.FULL
    args = argparse.Namespace(seed=21)
    smoke = chip_smoke.Smoke(args, size, compiles=None)
    client = new_tpu_evaluator()
    smoke.load(client)
    snap = client.store.snapshot_for(consistency.full())
    cs = snap.compiled
    calls = {}

    def record(kind, orig):
        def wrapped(q_cols, *a, **kw):
            if kind == "fused_probe":
                off, tbl = a
                g = {"off": _shape(off), "tbl": _shape(tbl),
                     "off_a": None if kw.get("off_a") is None
                     else _shape(kw["off_a"]),
                     "ashift": kw.get("ashift"), "cap": kw["cap"],
                     "over_vmem_ceiling": bool(over)}
                over.clear()
            else:
                tbls, caps, sw = a
                g = {"tbls": [_shape(t) for t in tbls],
                     "caps": list(caps), "sw": sw}
            g.update(
                kind=kind, nq=len(q_cols),
                B=int(np.prod(np.broadcast_shapes(
                    *[tuple(c.shape) for c in q_cols]))),
                spec=_jsonable(kw.get("spec")), mode=kw.get("mode", "block"),
                now=kw.get("now") is not None,
                gate=list(kw.get("gate", (False, False, False))),
                lay=kw.get("lay"),
            )
            calls[json.dumps(g, sort_keys=True)] = g
            return orig(q_cols, *a, **kw)

        return wrapped

    # record past the VMEM-residency ceiling instead of stopping at it:
    # the compiler's own answer at those shapes is part of the verdict
    over = []
    ceiling = P.require_vmem

    def note_over(name, *arrays):
        try:
            ceiling(name, *arrays)
        except ValueError:
            over.append(name)

    P.require_vmem = note_over
    P.fused_probe = record("fused_probe", P.fused_probe)
    P.fused_probe_aligned = record("fused_probe_aligned",
                                   P.fused_probe_aligned)
    B = 4096
    rng = np.random.default_rng(3)
    q_res = smoke.ids["document"][rng.integers(0, size["docs"], B)]
    q_subj = smoke.ids["user"][rng.integers(0, size["users"], B)]
    q_perm = np.full(B, smoke.view_slot, np.int32)
    for aligned in (True, False):
        engine = DeviceEngine(cs, EngineConfig.for_schema(
            cs, pallas=True, flat_aligned=aligned))
        dsnap = engine.prepare(snap)
        queries, qctx = engine._columns_preamble(
            dsnap, q_res.astype(np.int32), q_perm, q_subj.astype(np.int32),
            None, None, None, None)
        fn, fargs = engine.flat_fn_and_args(
            dsnap, queries, qctx, jnp.int32(0), B)
        jax.eval_shape(fn, *fargs)
        kern = spmv.kernels_for(engine, dsnap.flat_meta)
        keys = jax.ShapeDtypeStruct((1024,), jnp.int32)
        for k, (off_key, tbl_key) in {"rv": ("rv_off", "rvx"),
                                      "ra": ("ra_off", "rax"),
                                      "fw": ("fw_off", "fwx")}.items():
            if k in kern.raw_runs:
                off = dsnap.arrays[off_key]
                jax.eval_shape(kern.raw_runs[k], off,
                               dsnap.arrays.get(off_key + "_a", off),
                               dsnap.arrays[tbl_key], keys)
    # this world's programs reach only the block and runs modes (the
    # fold answers `view`; nothing is caveated or expiring).  The other
    # modes are recorded as they would run on a world with gate columns:
    # plain int32 rows of width 4 over the SAME offsets and row counts
    synth = (("any", {}), ("until2", {"now": True}),
             ("gate", {"now": True, "gate": [True, True, False],
                       "lay": {"exp": 2, "cav": 3}}))
    for g in [g for g in calls.values() if g["mode"] == "block"]:
        for mode, extra in synth:
            s = dict(g, mode=mode, spec=None, synthetic=True, **extra)
            if g["kind"] == "fused_probe":
                s["tbl"] = {"shape": [g["tbl"]["shape"][0], 4],
                            "dtype": "int32"}
            else:
                s["sw"] = 4
                s["tbls"] = [{"shape": [t["shape"][0], 4 * c],
                              "dtype": "int32"}
                             for t, c in zip(g["tbls"], g["caps"])]
            calls[json.dumps(s, sort_keys=True)] = s
    with open(path, "w") as f:
        json.dump({"edges": size["edges"], "calls": list(calls.values())},
                  f, indent=1, sort_keys=True)
    print(f"{len(calls)} distinct fused-probe calls → {path}",
          file=sys.stderr)


def lower_all(path: str) -> int:
    import jax
    import jax.numpy as jnp

    from gochugaru_tpu.engine import pallas as P

    with open(path) as f:
        geom = json.load(f)
    dev = jax.devices()[0]
    platform = {"platform": dev.platform, "kind": dev.device_kind}
    sds = lambda g: jax.ShapeDtypeStruct(tuple(g["shape"]), g["dtype"])
    refused = 0
    for g in geom["calls"]:
        kw = dict(spec=_tupled(g["spec"]), mode=g["mode"],
                  gate=tuple(g["gate"]), lay=g["lay"])
        qs = [jax.ShapeDtypeStruct((g["B"],), jnp.int32)] * g["nq"]
        now = [jax.ShapeDtypeStruct((), jnp.int32)] if g["now"] else []
        if g["kind"] == "fused_probe":
            tabs = [sds(g["off"]), sds(g["tbl"])] + (
                [sds(g["off_a"])] if g["off_a"] else [])

            def probe(q, nw, t, g=g, kw=kw):
                return P.fused_probe(
                    q, t[0], t[1], cap=g["cap"], ashift=g["ashift"],
                    off_a=t[2] if g["off_a"] else None, now=nw, **kw)
        else:
            tabs = [sds(t) for t in g["tbls"]]

            def probe(q, nw, t, g=g, kw=kw):
                return P.fused_probe_aligned(
                    q, t, g["caps"], g["sw"], now=nw, **kw)

        def call(*a, g=g, probe=probe):
            q, rest = a[:g["nq"]], list(a[g["nq"]:])
            return probe(q, rest.pop(0) if g["now"] else None, rest)

        line = {**platform, "kind_of_call": g["kind"], "mode": g["mode"],
                "B": g["B"], "packed": g["spec"] is not None,
                "synthetic": bool(g.get("synthetic")),
                "over_vmem_ceiling": bool(g.get("over_vmem_ceiling")),
                "tables": [t.shape for t in tabs]}
        try:
            jax.jit(call).lower(*qs, *now, *tabs).compile()
            line["verdict"] = "ok"
        except Exception as e:  # the refusal IS the result being recorded
            refused += 1
            msg = f"{type(e).__name__}: {e}"
            line["verdict"] = "refused"
            line["message"] = msg[:1500]
        print(json.dumps(line), flush=True)
    print(json.dumps({**platform, "calls": len(geom["calls"]),
                      "refused": refused, "edges": geom["edges"]}),
          flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dump-geometry", metavar="FILE")
    ap.add_argument("--tiny", action="store_true",
                    help="dump from chip_smoke's rehearsal world (tests)")
    ap.add_argument("--geometry", metavar="FILE")
    args = ap.parse_args()
    if bool(args.dump_geometry) == bool(args.geometry):
        ap.error("give exactly one of --dump-geometry / --geometry")
    if args.dump_geometry:
        dump_geometry(args.dump_geometry, args.tiny)
        return 0
    return lower_all(args.geometry)


if __name__ == "__main__":
    sys.exit(main())
