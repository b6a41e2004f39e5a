"""``worlds/docs.py``'s lookups: the strata are what they say on every seed,
and the plain lookup reference agrees with the plain check reference and
with the program's own host oracle."""

import numpy as np
import pytest

import run

CELL = "docs10m.lookup"


@pytest.fixture(scope="module")
def world():
    cell = run.load_cell(CELL, rehearse=True)
    return cell, cell["world"].build_world(cell["sizes"], 7)


def lookups_of(world, seed: int, strata=None):
    cell, w = world
    return cell["world"].make_lookups(
        w, cell["sizes"], np.random.default_rng([seed, 1]),
        strata or cell["traffic"]["strata"])


def test_every_seed_gives_the_same_strata_in_another_order(world):
    cell, _ = world
    mod, strata = cell["world"], cell["traffic"]["strata"]
    assert set(strata) == set(mod.LOOKUP_STRATA)
    drawn = [lookups_of(world, seed) for seed in (7, 7, 2**31 + 5)]
    for kinds, keys, names in drawn:
        assert {n: int((names == n).sum()) for n in strata} == strata
        assert all(kinds[names == n].tolist() == [mod.LOOKUP_STRATA[n][0]] * c
                   for n, c in strata.items())
        # distinct inside a stratum's draw
        assert np.unique(keys[names == "users"]).shape[0] == strata["users"]
        assert np.unique(keys[names == "documents"]).shape[0] == strata["documents"]
    assert all(np.array_equal(a, b) for a, b in zip(drawn[0], drawn[1]))
    assert not np.array_equal(drawn[0][1], drawn[2][1])
    assert not np.array_equal(drawn[0][2], drawn[2][2])  # shuffled by the seed
    # the full mix is 1,024 lookups, 640 / 112 / 16 / 256 (PR 35: four times
    # PR 31's, in its proportions; a viewer of each of the 16 level-1 folders)
    assert run.load_cell(CELL, rehearse=False)["traffic"]["strata"] == {
        "users": 640, "level2_viewers": 112, "level1_viewers": 16, "documents": 256}
    with pytest.raises(ValueError, match="level3_viewers"):
        lookups_of(world, 7, {"level3_viewers": 1})


def test_the_top_of_the_tree_has_one_shape_and_a_seed_draws_the_rest():
    """``fix_top``: on every seed the root and its 16 children are viewed by
    the same kinds (user / group) and the groups have the same number of
    groups nested below them; who they are differs; nothing else of the drawn
    world changes, and every folder keeps exactly one viewer edge."""
    cell = run.load_cell(CELL, rehearse=True)
    mod, sizes = cell["world"], cell["sizes"]
    shapes, whos = [], []
    for seed in (7, 9, 2**31 + 5):
        w, drawn = mod.build_world(sizes, seed), mod.draw_world(sizes, seed)
        for key in drawn:
            same = all(np.array_equal(a, b) for a, b in zip(drawn[key], w[key]))
            assert same == (key not in ("folder_group", "folder_user")), key
        (gf, gg), (uf, uu) = w["folder_group"], w["folder_user"]
        assert np.array_equal(np.sort(np.concatenate([gf, uf])),
                              np.arange(sizes["folders"]))
        assert np.all(np.diff(gf) > 0) and np.all(np.diff(uf) > 0)
        nested = dict(zip(*w["group_group"]))  # group -> the group nested in it

        def below(g):
            return 0 if g not in nested else 1 + below(nested[g])

        by_group = dict(zip(gf.tolist(), gg.tolist()))
        shapes.append([below(by_group[f]) if f in by_group else None
                       for f in range(17)])
        whos.append([by_group.get(f, -1) for f in range(17)])
        for key in ("folder_group", "folder_user"):  # below the top: as drawn
            (f, v), (df, dv) = w[key], drawn[key]
            assert np.array_equal(f[f >= 17], df[df >= 17])
            assert np.array_equal(v[f >= 17], dv[df >= 17])
    assert shapes[0] == shapes[1] == shapes[2] == [mod.top_viewer(f) for f in range(17)]
    assert shapes[0][0] == 2 and {4, 3, 0, None} <= set(shapes[0][1:])
    assert whos[0] != whos[1]


def test_every_seed_draws_its_users_from_the_same_size_classes(world):
    """``answer_bounds`` is what it says, from the edge lists alone (the sum of
    a user's direct documents, its groups' and the documents at or below what
    either views: never under the true answer, equal where nothing overlaps),
    and the ``users`` of two seeds hold every size class in the same number, to
    within one: the class's share of all users."""
    cell, w = world
    mod, sizes = cell["world"], cell["sizes"]
    bound = mod.answer_bounds(w, sizes)
    users = np.arange(0, sizes["users"], 7)
    true = np.array([a.shape[0] for a in mod.lookup_reference(w, sizes)(
        mod.RESOURCES, users)])
    assert np.all(bound[users] >= true) and np.mean(bound[users] == true) > 0.5
    below = mod.documents_below(w, sizes)
    assert below[0] == sizes["docs"] and below[1:17].sum() + np.sum(
        w["doc_folder"][1] == 0) == sizes["docs"]
    classes = mod.size_class(bound)
    share = np.bincount(classes) / classes.shape[0]
    for seed in (7, 11):
        _, keys, names = lookups_of(world, seed)
        mine = keys[names == "users"]
        drawn = np.bincount(classes[mine], minlength=share.shape[0])
        assert np.all(np.abs(drawn - share * mine.shape[0]) < 1)
    picked = mod.spread_over_classes(np.array([0] * 6 + [1] * 3 + [5]), 5,
                                     np.random.default_rng(1))
    assert sorted(np.array([0] * 6 + [1] * 3 + [5])[picked].tolist()) == [0, 0, 0, 1, 1]
    assert np.unique(picked).shape[0] == 5


@pytest.mark.parametrize("level,lo,hi", [(1, 1, 17), (2, 17, 273)])
def test_a_folder_stratum_user_views_a_folder_of_its_level(world, level, lo, hi):
    """From the edge lists alone: the user is the viewer of a folder of that
    level, or a member (directly or through nesting) of the group that is."""
    cell, w = world
    mod, sizes = cell["world"], cell["sizes"]
    assert mod.folders_of_level(level, sizes["folders"]) == (lo, hi)
    assert mod.folders_of_level(level, 100) == (lo, min(hi, 100))
    name = f"level{level}_viewers"
    _, users, names = lookups_of(world, 11, {name: 12})
    assert names.tolist() == [name] * 12
    fu_f, fu_u = w["folder_user"]
    fg_f, fg_g = w["folder_group"]
    direct = set(fu_u[(fu_f >= lo) & (fu_f < hi)].tolist())
    groups = set(fg_g[(fg_f >= lo) & (fg_f < hi)].tolist())
    nested = dict(zip(*w["group_group"]))  # group -> the group nested in it
    members = {}
    for g, u in zip(*w["group_user"]):
        members.setdefault(int(g), set()).add(int(u))
    through = set()
    for g in groups:
        while g is not None:
            through |= members.get(g, set())
            g = nested.get(g)
    assert all(u in direct or u in through for u in users.tolist())
    assert any(u in through and u not in direct for u in users.tolist())


def test_lookup_reference_lists_exactly_what_the_check_reference_grants(world):
    cell, w = world
    mod, sizes = cell["world"], cell["sizes"]
    kinds, keys, _ = lookups_of(world, 3)
    answer, check = mod.lookup_reference(w, sizes), mod.reference(w, sizes)
    users, docs = keys[kinds == mod.RESOURCES], keys[kinds == mod.SUBJECTS]
    every_doc, every_user = np.arange(sizes["docs"]), np.arange(sizes["users"])
    sizes_seen = []
    for user, got in zip(users.tolist(), answer(mod.RESOURCES, users)):
        want = np.nonzero(check(every_doc, np.full_like(every_doc, user)))[0]
        assert np.array_equal(got, want), user
        sizes_seen.append(got.shape[0])
    for doc, got in zip(docs.tolist(), answer(mod.SUBJECTS, docs)):
        want = np.nonzero(check(np.full_like(every_user, doc), every_user))[0]
        assert np.array_equal(got, want), doc
        assert got.shape[0] > 0
    # answers from a handful of ids to most of a folder tree
    assert min(sizes_seen) < 50 and max(sizes_seen) > 500
    assert answer(mod.RESOURCES, []) == []


def test_lookup_reference_agrees_with_the_programs_oracle(world):
    from gochugaru_tpu import consistency
    from gochugaru_tpu.engine.oracle import SnapshotOracle
    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(1)
    cell, w = world
    mod = cell["world"]
    program = run.Program(cell, w, lambda *a, **k: None)
    oracle = SnapshotOracle(program.client.store.snapshot_for(consistency.full()))
    pool = cell["entry"].requests(cell, w, np.random.default_rng([5, 1]))
    answer = cell["entry"].reference(cell, w)
    for req in pool:
        if req.kind == mod.RESOURCES:
            got = oracle.lookup_resources("document", "view", "user",
                                          f"u{req.key}", "")
            assert req.args == ("document#view", f"user:u{req.key}")
        else:
            got = oracle.lookup_subjects("document", f"d{req.key}", "view",
                                         "user", "")
            assert req.args == (f"document:d{req.key}", "view", "user")
        got = list(got)
        assert len(got) == len(set(got))
        assert sorted(got) == sorted(answer(req)), (req.stratum, req.key)


def test_the_judge_counts_missing_extra_repeated_and_malformed_ids(world):
    cell, w = world
    entry = cell["entry"]
    pool = entry.requests(cell, w, np.random.default_rng([5, 1]))
    right = entry.reference(cell, w)(pool[0])
    assert len(right) >= 2
    wrong_of = lambda out: entry.judge(cell, w, pool, [(0, 0.0, 1.0, out)], 0)
    ok = wrong_of(right[::-1])  # a set: the order is the program's own
    assert ok["wrong_answers"]["value"] == 0
    assert ok["answers_compared"]["value"] == len(right)
    assert wrong_of(right[1:])["wrong_answers"]["value"] == 1
    assert wrong_of(right + right[:1])["wrong_answers"]["value"] == 1
    assert wrong_of(right + ["document:d1"])["wrong_answers"]["value"] == 1
    assert wrong_of(entry.flipped(right))["wrong_answers"]["value"] >= 1
    assert wrong_of(entry.short(right))["wrong_answers"]["value"] == (
        len(right) - len(right) // 2)
    lost = entry.judge(cell, w, pool, [(0, 0.0, 1.0, TimeoutError())], 1)
    assert lost["unanswered_requests"]["value"] == 2
    assert entry.tally([right, []]) == {"lookups": 2, "ids": len(right)}
