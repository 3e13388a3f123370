"""Traffic generator: the Retwis op schedule drawn from a traffic file.

The store holds three objects per user: object ``3u`` is user ``u``'s
follower set, ``3u + 1`` its wall and ``3u + 2`` its timeline. Each node
runs ``ops_per_node`` operations a round. Each operation names one user,
drawn by Zipf over the users (rank-probability proportional to
``rank ** -zipf``), and a kind from the mix. Its updates follow the
paper's Table II:

* ``follow``: a second user, drawn from the same Zipf, follows the named
  user; 1 update, to the named user's follower set;
* ``post``: the named user posts; 1 + #followers updates, to its own wall
  and to the timeline of each of its followers;
* ``read``: a timeline read; no update.

The follower graph starts empty and grows by every follow; a post reaches
the followers of its user as of the end of the previous round.

One ``np.random.default_rng(seed)`` draws the users, then the kinds, then
the followers, in that order. The first two draws are those of the
program's ``sync/workloads.WorkloadSpec`` over the users, bit for bit, so
that both generators name the same operations; the program's generator
gives every operation one update and has no followers.
"""

from __future__ import annotations

import numpy as np

KINDS = ("follow", "post", "read")
FOLLOWERS, WALL, TIMELINE = 0, 1, 2         # object class within a user


def zipf_probs(users: int, zipf: float) -> np.ndarray:
    """Per-user probabilities [users], float64, summing to 1."""
    probs = np.arange(1, users + 1, dtype=np.float64) ** -zipf
    return probs / probs.sum()


def kind_probs(mix) -> np.ndarray:
    p = np.asarray([k["prob"] for k in mix], np.float64)
    s = p.sum()
    # An already-normalized mix is used as is: renormalizing would move the
    # sampling cdf by rounding and could change a seeded draw.
    return p if abs(s - 1.0) <= 1e-9 else p / s


def draws(traffic: dict, users: int, nodes: int, seed: int) -> tuple:
    """``(user, kind, other)``, each [T, N, ops]: the user each operation
    names, the index of its kind in the mix, and the follower a follow
    would add."""
    probs = zipf_probs(users, float(traffic["zipf"]))
    rng = np.random.default_rng(seed)
    shape = (int(traffic["active_rounds"]), nodes,
             int(traffic["ops_per_node"]))
    user = rng.choice(users, size=shape, p=probs)
    kind = rng.choice(len(traffic["mix"]), size=shape,
                      p=kind_probs(traffic["mix"]))
    other = rng.choice(users, size=shape, p=probs)
    return user, kind, other


def update_counts(traffic: dict, objects: int, nodes: int,
                  seed: int) -> np.ndarray:
    """Update counts [T, N, B] int32: how many updates node n applies to
    object b in active round t. ``traffic`` is a traffic file's dict
    (``active_rounds``, ``ops_per_node``, ``zipf``, ``mix``)."""
    rounds = int(traffic["active_rounds"])
    ops = int(traffic["ops_per_node"])
    mix = traffic["mix"]
    if min(objects, nodes, rounds, ops) < 1 or objects % 3:
        raise ValueError("objects must be a positive multiple of 3, and "
                         "nodes, active_rounds and ops_per_node >= 1")
    if sorted(k["name"] for k in mix) != sorted(KINDS):
        raise ValueError(f"the mix names {[k['name'] for k in mix]}; a "
                         f"Retwis mix names each of {KINDS} once")
    users = objects // 3
    user, kind, other = draws(traffic, users, nodes, seed)
    names = np.asarray([k["name"] for k in mix])[kind]

    upd = np.zeros((rounds, nodes, objects), np.int32)
    followers = [set() for _ in range(users)]
    for t in range(rounds):
        fol = names[t] == "follow"
        post = names[t] == "post"
        n_fol, _ = np.nonzero(fol)
        np.add.at(upd[t], (n_fol, 3 * user[t][fol] + FOLLOWERS), 1)
        n_post, _ = np.nonzero(post)
        np.add.at(upd[t], (n_post, 3 * user[t][post] + WALL), 1)
        for n, u in zip(n_post, user[t][post]):
            if followers[u]:
                fans = np.fromiter(followers[u], np.int64)
                upd[t, n, 3 * fans + TIMELINE] += 1
        for u, f in zip(user[t][fol], other[t][fol]):
            followers[u].add(int(f))
    return upd
