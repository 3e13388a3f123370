"""The traffic generator names the operations the program's generator
names, and gives each the updates of the paper's Table II."""

import numpy as np
import pytest

from bench import generator, spec


def traffic(name):
    return spec.load_json(spec.HERE / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", ["paper", "drain"])
@pytest.mark.parametrize("seed", [0, 17, 2**31 + 7])
def test_draws_match_program_generator(name, seed):
    """Users and kinds are the draws of ``workloads.retwis`` over the
    users, bit for bit."""
    from repro.sync import workloads

    t = traffic(name)
    users, nodes = 400, 12
    user, kind, _ = generator.draws(t, users, nodes, seed)
    targets, kinds = workloads.retwis(users, nodes, t["active_rounds"],
                                      t["ops_per_node"], t["zipf"],
                                      seed=seed).streams()
    np.testing.assert_array_equal(user, targets)
    np.testing.assert_array_equal(kind, kinds)
    assert [k["prob"] for k in t["mix"]] == [
        k.prob for k in workloads.RETWIS_MIX]


def plain_counts(t, objects, nodes, seed):
    """Table II, one operation at a time."""
    user, kind, other = generator.draws(t, objects // 3, nodes, seed)
    names = [k["name"] for k in t["mix"]]
    upd = np.zeros((t["active_rounds"], nodes, objects), np.int32)
    graph = {}
    for r in range(user.shape[0]):
        follows = []
        for n in range(nodes):
            for i in range(user.shape[2]):
                u, kname = int(user[r, n, i]), names[kind[r, n, i]]
                if kname == "follow":
                    upd[r, n, 3 * u] += 1
                    follows.append((u, int(other[r, n, i])))
                elif kname == "post":
                    upd[r, n, 3 * u + 1] += 1
                    for f in graph.get(u, ()):
                        upd[r, n, 3 * f + 2] += 1
        for u, f in follows:
            graph.setdefault(u, set()).add(f)
    return upd


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_counts_follow_table_ii(seed):
    t = traffic("paper")
    ours = generator.update_counts(t, 150, 6, seed)
    assert ours.dtype == np.int32
    np.testing.assert_array_equal(ours, plain_counts(t, 150, 6, seed))
    # posts reach followers: timelines take updates once follows exist
    assert ours[:, :, 2::3].sum() > 0


def test_same_seed_same_counts_other_seed_other_counts():
    t = traffic("paper")
    a = generator.update_counts(t, 300, 8, 5)
    np.testing.assert_array_equal(a, generator.update_counts(t, 300, 8, 5))
    assert not np.array_equal(a, generator.update_counts(t, 300, 8, 6))


def test_mix_must_be_retwis():
    t = dict(traffic("paper"), mix=[{"name": "write", "prob": 1.0}])
    with pytest.raises(ValueError):
        generator.update_counts(t, 30, 4, 0)
