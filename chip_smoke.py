#!/usr/bin/env python3
"""chip_smoke.py — the client's served path, once, on a real TPU.

The quickest proof that the system still starts on the chip.  With no
arguments it IS the chip run: anything but a TPU exits non-zero before
any work.  One process (it starts no child that needs the chip), the
public boundary only (``new_tpu_evaluator`` and the client it returns),
and no phase wrapped in a ``try`` that lets the run go on — a failure is
a traceback and a non-zero exit.

What it does:

1. loads BASELINE config 3 (BASELINE.md row 3; schema and shape as
   benchmarks/bench3_docs.py): 100k users, 10k groups nested 5 deep, 50k
   folders in arity-16 trees, 1M documents, 10M edges — generated from
   ``--seed`` with vectorised numpy and imported through ``write_schema``
   + ``import_relationship_id_columns``, one call per relation shape;
2. answers a few of each request the client serves (one 100k bulk check,
   warm small batches on every latency tier, a serving handle under
   concurrent submitters, one multi-hop lookup each way, one write read
   back at its token), on one device and — when four are visible — again
   through ``with_mesh(make_mesh(1, 4))`` (and, with ``--partitioned``,
   once more through ``with_mesh(..., partitioned=True)``);
3. compares every answer with the host reference ``engine/oracle.py``;
4. proves from ``utils/metrics.default`` that the device did the work:
   no oracle-served check, no host-resolved item, no retry, no breaker
   reroute, no compile on a warm shape;
5. prints one ``"event": "report"`` JSON line with everything the run
   learned (times in it are set-up facts of ONE run, labelled so; they
   are not benchmark numbers), and then, as the last line of stdout,
   exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
   the device as JAX reports it.

``--rehearse-cpu`` is the only way onto another backend: a tiny world on
four virtual CPU devices whose every output line says ``platform: "cpu"``
(on-chip-measurement guide §1 — make the command run end to end on the
CPU first, then send the same command at the real size).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

T_START = time.perf_counter()  # before the heavy imports: they are set-up

import numpy as np

# in a directory that holds nothing else of the repo this import fails:
# a traceback, a non-zero exit and no result, as the contract asks
import gochugaru_tpu
from gochugaru_tpu import consistency, native, new_tpu_evaluator, rel
from gochugaru_tpu.client import with_latency_mode, with_mesh, with_store
from gochugaru_tpu.engine.oracle import SnapshotOracle, T
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.parallel import make_mesh
from gochugaru_tpu.utils import metrics
from gochugaru_tpu.utils.context import background
from gochugaru_tpu.utils.platform import (
    configure_compile_cache,
    force_cpu_platform,
)

SCHEMA = """
definition user {}
definition group { relation member: user | group#member }
definition folder {
    relation parent: folder
    relation viewer: user | group#member
    permission view = viewer + parent->view
}
definition document {
    relation folder: folder
    relation viewer: user | group#member
    permission view = viewer + folder->view
}
"""

#: BASELINE config 3 at full scale — the chip run
FULL = dict(users=100_000, groups=10_000, folders=50_000, docs=1_000_000,
            edges=10_000_000, bulk=100_000)
#: the CPU rehearsal: same shape, 1% of the scale
TINY = dict(users=1_000, groups=100, folders=500, docs=10_000,
            edges=100_000, bulk=8_192)

GROUP_DEPTH = 5  # nesting chains break every 5 groups
FOLDER_ARITY = 16
MEMBERS_PER_GROUP = 6
TIERS = (256, 1_024, 4_096)  # EngineConfig.latency_tiers
BULK_SAMPLES = 1_000  # oracle comparisons (the oracle is Python)
TIER_SAMPLES = 200
SERVE_SAMPLES = 200
SUBMITTERS = 4
SERVE_ROUNDS = 8
#: every call carries a deadline: RESOURCE_EXHAUSTED classifies as
#: transient (utils/errors.py), so an HBM overflow would otherwise be
#: retried under backoff and look like a hang
FIRST_CALL_DEADLINE_S = 900.0  # includes the device prepare
CALL_DEADLINE_S = 180.0  # floor; see Smoke.call_deadline_s
MIN_RESIDENT_BYTES = 1 << 30  # the chip run must hold a real deployment

PLATFORM = "unknown"  # set once JAX has chosen; on every output line


def say(event: str, **fields) -> None:
    print(json.dumps({"platform": PLATFORM, "event": event, **fields}),
          flush=True)


class CompileWatch:
    """Counts what JAX itself reports: compile requests (a jit cache
    miss reached the compiler), their seconds, persistent-cache hits."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.requests, self.seconds, self.cache_hits)

    def since(self, mark) -> dict:
        return {
            "compile_requests": self.requests - mark[0],
            "compile_s": round(self.seconds - mark[1], 3),
            "persistent_cache_hits": self.cache_hits - mark[2],
        }


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ---------------------------------------------------------------------------
# the world: BASELINE config 3, index space (ids are d<i>, u<i>, ...)
# ---------------------------------------------------------------------------


def unique_pairs(a, b):
    """Drop duplicate (a, b) pairs (an import refuses live duplicates)."""
    key = np.unique(a.astype(np.int64) << 32 | b.astype(np.int64))
    return (key >> 32).astype(np.int64), (key & 0xFFFFFFFF).astype(np.int64)


def build_world(size: dict, seed: int) -> dict:
    """Edge lists per relation shape, as index pairs, exactly
    ``size['edges']`` edges in total."""
    rng = np.random.default_rng(seed)
    U, G, F, D = size["users"], size["groups"], size["folders"], size["docs"]
    w = {}
    g = np.arange(G - 1)
    deep = g[(g % GROUP_DEPTH) != GROUP_DEPTH - 1]
    w["group_group"] = (deep, deep + 1)
    w["group_user"] = unique_pairs(
        np.repeat(np.arange(G), MEMBERS_PER_GROUP),
        rng.integers(0, U, G * MEMBERS_PER_GROUP),
    )
    f = np.arange(1, F)
    w["folder_parent"] = (f, (f - 1) // FOLDER_ARITY)
    by_group = rng.random(F) < 0.5
    w["folder_group"] = (np.nonzero(by_group)[0],
                         rng.integers(0, G, int(by_group.sum())))
    w["folder_user"] = (np.nonzero(~by_group)[0],
                        rng.integers(0, U, int((~by_group).sum())))
    w["doc_folder"] = (np.arange(D), rng.integers(0, F, D))
    base = sum(a.shape[0] for a, _ in w.values())
    # top up with group viewers spread evenly over the documents (as
    # bench3 does: per-document userset fan-in stays within the engine's
    # leaf cap), the rest as direct viewers
    per_doc = max((size["edges"] - base - D // 5) // D, 0)
    w["doc_group"] = unique_pairs(
        np.repeat(np.arange(D), per_doc), rng.integers(0, G, D * per_doc)
    )
    n_direct = size["edges"] - base - w["doc_group"][0].shape[0]
    if n_direct < 0:
        raise ValueError("edge target below the world's fixed edges")
    dd, du = unique_pairs(
        rng.integers(0, D, n_direct + n_direct // 16 + 64),
        rng.integers(0, U, n_direct + n_direct // 16 + 64),
    )
    keep = rng.permutation(dd.shape[0])[:n_direct]
    if keep.shape[0] != n_direct:
        raise ValueError("could not draw enough distinct direct viewers")
    w["doc_user"] = (dd[keep], du[keep])
    # per-folder viewer (exactly one each) for probe construction
    fv_group = np.full(F, -1, np.int64)
    fv_user = np.full(F, -1, np.int64)
    fv_group[w["folder_group"][0]] = w["folder_group"][1]
    fv_user[w["folder_user"][0]] = w["folder_user"][1]
    w["_fv_group"], w["_fv_user"] = fv_group, fv_user
    # group → direct members CSR (group_user is sorted by group)
    gu_g, gu_u = w["group_user"]
    w["_gm_start"] = np.searchsorted(gu_g, np.arange(G + 1))
    w["_gm_user"] = gu_u
    return w


def member_of(w, rng, groups):
    """One user per group in ``groups`` who is a member of it, half of
    them through a nested descendant (g ⊇ g+1 ⊇ … inside a chain)."""
    G = w["_gm_start"].shape[0] - 1
    room = (GROUP_DEPTH - 1) - (groups % GROUP_DEPTH)
    room = np.minimum(room, G - 1 - groups)
    hop = np.where(rng.random(groups.shape[0]) < 0.5,
                   (rng.random(groups.shape[0]) * (room + 1)).astype(np.int64),
                   0)
    g = groups + hop
    lo, hi = w["_gm_start"][g], w["_gm_start"][g + 1]
    pick = lo + (rng.random(g.shape[0]) * (hi - lo)).astype(np.int64)
    return w["_gm_user"][pick]


def make_probes(w, size: dict, rng, n: int):
    """``n`` (document, user) probes: a quarter uniform (mostly denied),
    a quarter direct viewers, a quarter members of a viewer group (half
    of those through nesting), a quarter viewers of an ancestor folder —
    so every hop of the 5-hop rewrite is exercised both ways."""
    D, U = size["docs"], size["users"]
    q = n // 4
    docs = [rng.integers(0, D, n - 3 * q)]
    users = [rng.integers(0, U, n - 3 * q)]
    pick = rng.integers(0, w["doc_user"][0].shape[0], q)
    docs.append(w["doc_user"][0][pick])
    users.append(w["doc_user"][1][pick])
    pick = rng.integers(0, w["doc_group"][0].shape[0], q)
    docs.append(w["doc_group"][0][pick])
    users.append(member_of(w, rng, w["doc_group"][1][pick]))
    d = rng.integers(0, D, q)
    anc = w["doc_folder"][1][d]
    for _ in range(4):  # climb 0..4 levels (roots stay put)
        up = (rng.random(q) < 0.5) & (anc > 0)
        anc = np.where(up, (anc - 1) // FOLDER_ARITY, anc)
    by_group = w["_fv_group"][anc] >= 0
    u = np.where(by_group, 0, w["_fv_user"][anc])
    u[by_group] = member_of(w, rng, w["_fv_group"][anc][by_group])
    docs.append(d)
    users.append(u)
    order = rng.permutation(n)
    return np.concatenate(docs)[order], np.concatenate(users)[order]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args, size: dict, compiles: CompileWatch) -> None:
        self.m = metrics.default
        self.args, self.size, self.compiles = args, size, compiles
        self.rng = np.random.default_rng(args.seed + 1)
        self.world = None
        self.ids = {}  # type → node-id column from this store's interner
        self.view_slot = None
        #: admission control learns its cost estimate from admitted
        #: dispatches, the cold prepare included, and sheds a request
        #: whose deadline is under the estimate (utils/admission.py) —
        #: so later deadlines stay above twice the first call's time
        self.call_deadline_s = CALL_DEADLINE_S

    # -- helpers ---------------------------------------------------------
    def ctx(self, seconds: float = 0.0):
        return background().with_timeout(seconds or self.call_deadline_s)

    def rels_of(self, docs, users):
        mk = rel.must_from_triple
        return [mk(f"document:d{d}", "view", f"user:u{u}")
                for d, u in zip(docs.tolist(), users.tolist())]

    def counters(self) -> dict:
        m = self.m
        return {k: m.counter(k) for k in (
            "checks.requested", "checks.device_definite", "checks.oracle",
            "checks.fallback_overflow", "checks.fallback_conditional",
            "retry.retries", "admission.deadline_sheds", "admission.sheds",
            "breaker.latency_rerouted",
            "latency.compiles", "latency.dispatches", "latency.retraces",
            "lookups.frontier", "lookups.fused", "lookups.walker",
            "spmm.dispatches", "spmm.fallbacks", "lookup.dispatches",
            "serve.batches",
        )}

    def timed(self, call, key: str = "first_call_wall_s"):
        """(result, {wall seconds under ``key``, what JAX compiled})."""
        mark, t0 = self.compiles.mark(), time.perf_counter()
        result = call()
        return result, {key: round(time.perf_counter() - t0, 3),
                        **self.compiles.since(mark)}

    def prepare_stages(self) -> dict:
        """Cumulative seconds of every ``prepare.*_s`` stage timer."""
        return {k[:-len(".total_s")]: v
                for k, v in sorted(self.m.snapshot().items())
                if k.startswith("prepare.") and k.endswith(".total_s")}

    def oracle_at(self, cs):
        """The plain reference over the store's host columns —
        independent of every device table."""
        return SnapshotOracle(self.store.snapshot_for(cs))

    def must_agree(self, what: str, oracle, rels, got, sample: int) -> int:
        n = len(rels)
        idx = (self.rng.permutation(n)[:sample] if sample < n
               else np.arange(n))
        bad = [int(i) for i in idx
               if (oracle.check_relationship(rels[i]) == T) != bool(got[i])]
        if bad:
            raise AssertionError(
                f"{what}: {len(bad)} of {len(idx)} answers differ from the"
                f" oracle, first {rels[bad[0]]} → device {got[bad[0]]}"
            )
        return int(len(idx))

    # -- step 2: load ------------------------------------------------------
    def load(self, client) -> dict:
        t0 = time.perf_counter()
        w = self.world = build_world(self.size, self.args.seed)
        t_gen = time.perf_counter() - t0
        ctx = self.ctx()
        client.write_schema(ctx, SCHEMA)
        self.store = client.store
        itn = self.store.interner
        t0 = time.perf_counter()
        for tname, prefix, key in (("user", "u", "users"),
                                   ("group", "g", "groups"),
                                   ("folder", "f", "folders"),
                                   ("document", "d", "docs")):
            self.ids[tname] = itn.node_batch(
                tname, [f"{prefix}{i}" for i in range(self.size[key])]
            )
        t_intern = time.perf_counter() - t0
        shapes = (  # (edges, resource type, relation, subject type, srel)
            ("group_group", "group", "member", "group", "member"),
            ("group_user", "group", "member", "user", ""),
            ("folder_parent", "folder", "parent", "folder", ""),
            ("folder_group", "folder", "viewer", "group", "member"),
            ("folder_user", "folder", "viewer", "user", ""),
            ("doc_folder", "document", "folder", "folder", ""),
            ("doc_group", "document", "viewer", "group", "member"),
            ("doc_user", "document", "viewer", "user", ""),
        )
        t0 = time.perf_counter()
        edges = 0
        for key, rtype, relation, stype, srel in shapes:
            r, s = w[key]
            client.import_relationship_id_columns(
                self.ctx(), resource_ids=self.ids[rtype][r],
                resource_relation=relation, subject_ids=self.ids[stype][s],
                subject_relation=srel,
            )
            edges += int(r.shape[0])
        t_import = time.perf_counter() - t0
        if edges != self.size["edges"]:
            raise AssertionError(f"imported {edges} edges")
        self.view_slot = self.store.compiled_schema.slot_of_name["view"]
        out = {"edges": edges, "generate_s": round(t_gen, 3),
               "intern_s": round(t_intern, 3), "import_s": round(t_import, 3)}
        say("loaded", **out)
        return out

    # -- steps 3-5 on one client -------------------------------------------
    def serve(self, client, label: str, lookups_on: str) -> dict:
        """``lookups_on`` names the engine that must serve the lookups:
        ``fused`` (one-dispatch SpMM, single device), ``frontier``
        (owner-routed per-hop device frontier, sharded tables) or
        ``walker`` (host candidates + device checks: the partitioned
        feed declines the reverse index, engine/partition.py)."""
        full = consistency.full()
        before = self.counters()
        out = {"label": label}
        oracle = self.oracle_at(full)

        # first request: the device prepare + the first program
        d0, u0 = make_probes(self.world, self.size, self.rng, 4)
        stages0 = self.prepare_stages()
        _, first = self.timed(lambda: client.check(
            self.ctx(FIRST_CALL_DEADLINE_S), full, *self.rels_of(d0, u0)),
            key="wall_s")
        self.call_deadline_s = max(self.call_deadline_s, 2 * first["wall_s"])
        out["first_check"] = {
            **first,
            **{k: round(v - stages0.get(k, 0.0), 3)
               for k, v in self.prepare_stages().items()},
        }
        out["device_bytes_gauge"] = int(self.m.gauge("snapshot.device_bytes"))
        say("prepared", label=label, **out["first_check"],
            device_bytes=out["device_bytes_gauge"])

        # one bulk check, BASELINE's 100k batch (throughput path)
        B = self.size["bulk"]
        bd, bu = make_probes(self.world, self.size, self.rng, B)
        bulk_rels = self.rels_of(bd, bu)
        bulk, cost = self.timed(
            lambda: client.check(self.ctx(), full, *bulk_rels))
        out["bulk"] = {
            "checks": B, "allowed": int(sum(bulk)), **cost,
            "oracle_samples": self.must_agree(
                "bulk check", oracle, bulk_rels, bulk, BULK_SAMPLES),
        }
        say("bulk", label=label, **out["bulk"])

        # warm small batches, every tier, each shape dispatched twice
        out["tiers"] = {}
        for tier in TIERS:
            td, tu = make_probes(self.world, self.size, self.rng, tier)
            rels = self.rels_of(td, tu)
            first, cold = self.timed(
                lambda: client.check(self.ctx(), full, *rels))
            mark, pins = self.compiles.mark(), self.m.counter("latency.compiles")
            second = client.check(self.ctx(), full, *rels)
            warm = self.compiles.since(mark)
            warm_pins = self.m.counter("latency.compiles") - pins
            if warm["compile_requests"] or warm_pins or first != second:
                raise AssertionError(
                    f"tier {tier}: warm dispatch compiled ({warm},"
                    f" pins {warm_pins}) or changed its answer"
                )
            out["tiers"][str(tier)] = {
                **cold, "allowed": int(sum(first)),
                "warm_compile_requests": 0,
                "oracle_samples": self.must_agree(
                    f"tier {tier}", oracle, rels, first, TIER_SAMPLES),
            }
        say("tiers", label=label, **out["tiers"])

        # a serving handle under concurrent submitters: blocking check()
        # callers and open-loop submit_columns() callers side by side
        sd, su = make_probes(self.world, self.size, self.rng,
                             SUBMITTERS * SERVE_ROUNDS * 64)
        lanes = np.array_split(np.arange(sd.shape[0]), SUBMITTERS)
        mark = self.compiles.mark()
        with client.with_serving(cs=full) as handle:

            def by_rels(lane):
                got = []
                for part in np.array_split(lane, SERVE_ROUNDS):
                    part = part[:24]
                    rels = self.rels_of(sd[part], su[part])
                    got.append((part, handle.check(self.ctx(), *rels)))
                return got

            def by_columns(lane):
                got = []
                for part in np.array_split(lane, SERVE_ROUNDS):
                    fut = handle.submit_columns(
                        self.ctx(),
                        self.ids["document"][sd[part]].astype(np.int32),
                        np.full(part.shape[0], self.view_slot, np.int32),
                        self.ids["user"][su[part]].astype(np.int32),
                    )
                    got.append((part, fut.result(self.ctx())))
                return got

            with ThreadPoolExecutor(SUBMITTERS) as pool:
                futs = [pool.submit(by_rels if i % 2 == 0 else by_columns,
                                    lane) for i, lane in enumerate(lanes)]
                served = [pair for f in futs for pair in f.result()]
        idx = np.concatenate([p for p, _ in served])
        ans = np.concatenate([np.asarray(a, bool) for _, a in served])
        out["served"] = {
            "checks": int(idx.shape[0]), "allowed": int(ans.sum()),
            "submitters": SUBMITTERS, **self.compiles.since(mark),
            "oracle_samples": self.must_agree(
                "served", oracle, self.rels_of(sd[idx], su[idx]), ans,
                SERVE_SAMPLES),
        }
        say("served", label=label, **out["served"])

        # lookups that need more than one hop, compared as whole sets
        out["lookups"] = self.lookups(client, oracle, full)
        say("lookups", label=label, **out["lookups"])

        # an acknowledged write is read back; the old revision still denies
        out["write"] = self.write_read_back(client, bulk_rels, bulk)
        say("write", label=label, **out["write"])

        after = self.counters()
        delta = {k: after[k] - before[k] for k in after}
        out["counters"] = delta
        for k in ("checks.oracle", "checks.fallback_overflow",
                  "checks.fallback_conditional", "retry.retries",
                  "breaker.latency_rerouted", "latency.retraces"):
            if delta[k]:
                raise AssertionError(f"{label}: {k} = {delta[k]}, want 0")
        served_by = {k: delta[f"lookups.{k}"]
                     for k in ("fused", "frontier", "walker")}
        want = {"fused": (2, 2, 0), "frontier": (0, 2, 0),
                "walker": (0, 0, 2)}[lookups_on]
        if tuple(served_by.values()) != want:
            raise AssertionError(
                f"{label}: lookups served by {served_by}, want {lookups_on}")
        say("counters", label=label, **delta)
        return out

    def lookups(self, client, oracle, cs) -> dict:
        w = self.world
        # a folder high in its tree whose viewer is a group with nested
        # descendants; the subject is a member of the DEEPEST one, so the
        # answer needs group nesting AND the parent arrows below the folder
        fg_f, fg_g = w["folder_group"]
        ok = (fg_f < max(self.size["folders"] // FOLDER_ARITY, 1)) & (
            fg_g % GROUP_DEPTH == 0) & (fg_g + GROUP_DEPTH <= self.size["groups"])
        if not ok.any():
            raise AssertionError("no multi-hop lookup seed in this world")
        folder, top = int(fg_f[ok][0]), int(fg_g[ok][0])
        deepest = top + GROUP_DEPTH - 1
        user = int(w["_gm_user"][w["_gm_start"][deepest]])
        got, cost = self.timed(lambda: set(client.lookup_resources(
            self.ctx(), cs, "folder#view", f"user:u{user}")))
        res = {"subject": f"user:u{user}", "results": len(got), **cost}
        want = set(oracle.lookup_resources("folder", "view", "user",
                                           f"u{user}"))
        if got != want or f"f{folder}" not in got or len(got) < 2:
            raise AssertionError(
                f"lookup_resources: device {len(got)} ids, oracle"
                f" {len(want)}, symmetric difference"
                f" {sorted(got ^ want)[:5]}"
            )
        # a document in that folder: its viewers come through its own
        # groups, the folder's group and every ancestor folder
        docs_in = np.nonzero(w["doc_folder"][1] == folder)[0]
        doc = int(docs_in[0]) if docs_in.size else 0
        got_s, cost = self.timed(lambda: set(client.lookup_subjects(
            self.ctx(), cs, f"document:d{doc}", "view", "user")))
        sub = {"resource": f"document:d{doc}", "results": len(got_s), **cost}
        want_s = set(oracle.lookup_subjects(
            "document", f"d{doc}", "view", "user"))
        if got_s != want_s or len(got_s) < 2:
            raise AssertionError(
                f"lookup_subjects: device {len(got_s)} ids, oracle"
                f" {len(want_s)}, symmetric difference"
                f" {sorted(got_s ^ want_s)[:5]}"
            )
        return {"resources": res, "subjects": sub}

    def write_read_back(self, client, rels, answers) -> dict:
        # a probe the device denied: grant it, read it back at the token
        probe = next(r for r, a in zip(rels, answers) if not a)
        old = client.read_schema(self.ctx())[1]
        grant = rel.must_from_triple(
            f"{probe.resource_type}:{probe.resource_id}", "viewer",
            f"{probe.subject_type}:{probe.subject_id}")
        txn = rel.Txn()
        txn.create(grant)

        def write_then_read():
            token = client.write(self.ctx(), txn)
            return token, client.check(
                self.ctx(), consistency.at_least(token), probe)[0]

        (token, at_token), cost = self.timed(
            write_then_read, key="write_and_read_wall_s")
        at_old = client.check(self.ctx(), consistency.snapshot(old), probe)[0]
        want_new = self.oracle_at(consistency.at_least(token)).check_relationship(
            probe) == T
        if not (at_token and want_new) or at_old:
            raise AssertionError(
                f"write {grant} at {token}: read back {at_token} (oracle"
                f" {want_new}), old revision {old} answers {at_old}"
            )
        return {"wrote": str(grant), "token": token, "read_back": True,
                "old_revision": old, "old_revision_denies": True, **cost}


def per_device_bytes() -> dict:
    """Live bytes per device, from every live array's addressable
    shards (what this process holds there, tables included)."""
    import jax

    out = {str(d.id): 0 for d in jax.devices()}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            out[str(s.device.id)] += int(s.data.nbytes)
    return out


def main(argv=None) -> int:
    global PLATFORM
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny world on 4 virtual CPU devices; never the"
                         " default and never a chip result")
    ap.add_argument("--partitioned", action="store_true",
                    help="with 4 devices, also run with_mesh(partitioned=True)")
    args = ap.parse_args(argv)

    if args.rehearse_cpu:
        force_cpu_platform(4)
    import jax

    PLATFORM = jax.default_backend()
    if PLATFORM != ("cpu" if args.rehearse_cpu else "tpu"):
        # nothing on stdout: a run without the chip prints no result
        print(f"chip_smoke: JAX found backend {PLATFORM!r}, not a TPU;"
              " nothing was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("start", jax=jax.__version__, device=device, seed=args.seed)
    size = TINY if args.rehearse_cpu else FULL

    cache_dir = configure_compile_cache()
    cache_before = cache_entries(cache_dir)
    # no binary found in the tree is trusted: ingest.cpp is built here
    t0 = time.perf_counter()
    if not (native.rebuild() and native.available()):
        print("chip_smoke: native ingest library failed to build",
              file=sys.stderr)
        return 3
    t_native = time.perf_counter() - t0
    t_imports = time.perf_counter() - T_START - t_native
    say("imports", import_s=round(t_imports, 3),
        native_build_s=round(t_native, 3), compile_cache_dir=cache_dir,
        compile_cache_entries=cache_before)

    compiles = CompileWatch()
    smoke = Smoke(args, size, compiles)
    client = new_tpu_evaluator(with_latency_mode())
    load = smoke.load(client)
    choices = EngineConfig.for_schema(smoke.store.compiled_schema).resolved()
    say("resolved", **choices)

    sections = [smoke.serve(client, "one-device", lookups_on="fused")]
    stats = devices[0].memory_stats() or {}
    resident = {
        "table_bytes_gauge": sections[0]["device_bytes_gauge"],
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    }
    if not args.rehearse_cpu and (
        resident["table_bytes_gauge"] < MIN_RESIDENT_BYTES
    ):
        raise AssertionError(f"resident tables too small: {resident}")

    if len(devices) >= 4:
        # the same store, the same path, the tables split over the model
        # axis.  Drop the one-device client first so what each device
        # holds afterwards is the mesh client's tables and nothing else
        mesh = make_mesh(1, 4)
        modes = [False] + ([True] if args.partitioned else [])
        for partitioned in modes:
            del client
            gc.collect()
            floor = per_device_bytes()
            client = new_tpu_evaluator(
                with_latency_mode(), with_store(smoke.store),
                with_mesh(mesh, partitioned=partitioned),
            )
            label = "mesh-1x4" + ("-partitioned" if partitioned else "")
            sec = smoke.serve(client, label, lookups_on=(
                "walker" if partitioned else "frontier"))
            held = per_device_bytes()
            sec["per_device_bytes"] = {
                d: held[d] - floor[d] for d in sorted(held)
            }
            total = sum(sec["per_device_bytes"].values())
            worst = max(sec["per_device_bytes"].values())
            if total <= 0 or worst * 2 > total:
                raise AssertionError(
                    f"{label}: one device holds more than half of the"
                    f" table bytes: {sec['per_device_bytes']}"
                )
            say("mesh", label=label, per_device_bytes=sec["per_device_bytes"])
            sections.append(sec)

    report = {
        "jax": jax.__version__,
        "gochugaru_tpu": gochugaru_tpu.__version__,
        "seed": args.seed,
        "edges": load["edges"],
        "resident_bytes": resident,
        "resolved": choices,
        "native_available": native.available(),
        "setup_s_one_run": {
            "imports": round(t_imports, 3),
            "native_build": round(t_native, 3),
            "generate": load["generate_s"], "intern": load["intern_s"],
            "import": load["import_s"],
            "total_wall": round(time.perf_counter() - T_START, 3),
        },
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_entries(cache_dir)},
        "sections": sections,
    }
    say("report", device=device, **report)
    # the last line is the driver's contract: these keys and no others.
    # Everything the run learned is on the "report" line above it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
