"""Plain reference of one embedding refresh, in numpy float64.

The refresh is the technique of Brandeis et al. 2020, "About Graph
Degeneracy, Representation Learning and Scalability" (arXiv 2009.02085):
random walks and SkipGram with negative sampling (SGNS) run only on the
k0-core, with CoreWalk walk budgets, and every shell below k0 is then filled
by mean propagation (its §2.2). The service adds a warm start from the
stored table, an orthogonal Procrustes alignment back into the stored space
and a swap of the result into the store.

Everything here is written for reading, not for speed: no kernels, no
batching beyond one SGNS step, and nothing imported but numpy. Each
function takes plain arrays, so the same answers can be asked of any
implementation.

Departures from the published description, each the service's own:

* SGNS takes mini-batch steps of Adam (lr 0.025, b1 0.9, b2 0.999, eps
  1e-8, bias-corrected) on the batch's mean loss, in place of word2vec's
  per-pair SGD with a linearly decayed rate.
* The walks are uniform (DeepWalk; node2vec with p = q = 1), and a step
  takes a uniformly drawn context offset in [1, window] with a random sign,
  reflected at the walk's ends; this reference checks walks and one step on
  a given batch, not the sampler.
* Propagation makes a fixed number of Jacobi sweeps per shell, from zero,
  as the paper's iterative averaging does, and not the exact solve.
* A rotation in d dimensions is fitted only to at least d anchors; with
  fewer the table is left as it is.
"""
from __future__ import annotations

import numpy as np

__all__ = ["corewalk_budgets", "walk_faults", "sgns_loss", "sgns_step",
           "procrustes", "align", "propagate"]


def corewalk_budgets(core: np.ndarray, k0: int, n_walks: int) -> np.ndarray:
    """Walks rooted at each node: CoreWalk's max(floor(n * k_v / k_max), 1)
    on the k0-core (k_max the degeneracy), none below it."""
    core = np.asarray(core, np.int64)
    k_max = max(int(core.max()), 1)
    per = np.maximum(n_walks * core // k_max, 1)
    return np.where(core >= k0, per, 0)


def walk_faults(walks: np.ndarray, n: int, edges: np.ndarray,
                core: np.ndarray, k0: int, n_walks: int) -> int:
    """Faults of a refresh's real walks (W, L) on the graph ``edges``:
    steps that are not edges of its k0-core, walks that do not start at the
    root the budgets give them in order, and per-node walk counts that
    differ from the CoreWalk budget."""
    walks = np.asarray(walks, np.int64)
    core = np.asarray(core, np.int64)
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[(core[e[:, 0]] >= k0) & (core[e[:, 1]] >= k0)]
    keys = set((np.minimum(e[:, 0], e[:, 1]) * n
                + np.maximum(e[:, 0], e[:, 1])).tolist())
    a, b = walks[:, :-1].ravel(), walks[:, 1:].ravel()
    steps = (np.minimum(a, b) * n + np.maximum(a, b)).tolist()
    bad = sum(1 for k in steps if k not in keys)
    budget = corewalk_budgets(core, k0, n_walks)
    roots = np.repeat(np.arange(len(budget)), budget)
    m = min(len(roots), len(walks))
    bad += int(np.sum(walks[:m, 0] != roots[:m]))
    got = np.bincount(walks[:, 0], minlength=len(budget))[: len(budget)]
    bad += int(np.abs(got - budget).sum())
    return bad


def _softplus(z):
    return np.logaddexp(0.0, z)


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def sgns_loss(emb_in, emb_out, centers, contexts, negatives) -> float:
    """Mean over the batch of softplus(-c.x) + sum_k softplus(c.n_k)."""
    c = np.asarray(emb_in, np.float64)[centers]
    x = np.asarray(emb_out, np.float64)[contexts]
    ng = np.asarray(emb_out, np.float64)[negatives]  # (B, K, D)
    pos = np.sum(c * x, axis=1)
    neg = np.einsum("bkd,bd->bk", ng, c)
    return float(np.mean(_softplus(-pos) + _softplus(neg).sum(axis=1)))


def sgns_step(emb_in, emb_out, mu, nu, count: int, centers, contexts,
              negatives, *, lr: float, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8):
    """One SGNS step: the batch's mean loss, its gradients with respect to
    both tables, and Adam's update of both.

    ``mu`` and ``nu`` are dicts of Adam's moments keyed ``emb_in`` and
    ``emb_out``; ``count`` the steps taken. Returns ``(loss, tables, mu,
    nu)`` with the loss before the update and the tables after it.
    """
    tables = {"emb_in": np.array(emb_in, np.float64),
              "emb_out": np.array(emb_out, np.float64)}
    centers = np.asarray(centers, np.int64)
    contexts = np.asarray(contexts, np.int64)
    negatives = np.asarray(negatives, np.int64)
    B = len(centers)
    c = tables["emb_in"][centers]
    x = tables["emb_out"][contexts]
    ng = tables["emb_out"][negatives]
    pos = np.sum(c * x, axis=1)  # (B,)
    neg = np.einsum("bkd,bd->bk", ng, c)  # (B, K)
    loss = float(np.mean(_softplus(-pos) + _softplus(neg).sum(axis=1)))
    dpos = (_sigmoid(pos) - 1.0) / B  # d loss / d pos
    dneg = _sigmoid(neg) / B  # d loss / d neg
    grads = {k: np.zeros_like(v) for k, v in tables.items()}
    np.add.at(grads["emb_in"], centers,
              dpos[:, None] * x + np.einsum("bk,bkd->bd", dneg, ng))
    np.add.at(grads["emb_out"], contexts, dpos[:, None] * c)
    np.add.at(grads["emb_out"], negatives, dneg[:, :, None] * c[:, None, :])
    t = int(count) + 1
    mu_new, nu_new = {}, {}
    for k, g in grads.items():
        mu_new[k] = b1 * np.asarray(mu[k], np.float64) + (1 - b1) * g
        nu_new[k] = b2 * np.asarray(nu[k], np.float64) + (1 - b2) * g * g
        m_hat = mu_new[k] / (1 - b1 ** t)
        v_hat = nu_new[k] / (1 - b2 ** t)
        tables[k] = tables[k] - lr * m_hat / (np.sqrt(v_hat) + eps)
    return loss, tables, mu_new, nu_new


def procrustes(X: np.ndarray, Y: np.ndarray) -> np.ndarray:
    """The orthogonal R minimising ||X R - Y||: U V^T of the SVD of X^T Y."""
    U, _, Vt = np.linalg.svd(np.asarray(X, np.float64).T
                             @ np.asarray(Y, np.float64))
    return U @ Vt


def align(table: np.ndarray, anchors: np.ndarray,
          anchor_vecs: np.ndarray) -> np.ndarray:
    """``table`` rotated onto the anchors' previous vectors; unchanged when
    there are fewer anchors than dimensions."""
    table = np.asarray(table, np.float64)
    anchors = np.asarray(anchors, np.int64)
    if len(anchors) < table.shape[1]:
        return table
    return table @ procrustes(table[anchors], anchor_vecs)


def propagate(n: int, edges: np.ndarray, core: np.ndarray, k0: int,
              x: np.ndarray, n_iters: int) -> np.ndarray:
    """§2.2: rows of the k0-core kept, each shell k = k0-1, k0-2, ... down
    to the lowest core present set to zero and then, ``n_iters`` times at
    once, to the mean of its neighbours of core >= k (zero without any)."""
    core = np.asarray(core, np.int64)
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    src = np.concatenate([e[:, 0], e[:, 1]])
    dst = np.concatenate([e[:, 1], e[:, 0]])
    x = np.array(x, np.float64)
    for k in range(k0 - 1, int(core.min()) - 1, -1):
        shell = core == k
        if not shell.any():
            continue
        use = shell[src] & (core[dst] >= k)
        order = np.argsort(src[use], kind="stable")
        s, d = src[use][order], dst[use][order]
        rows, starts, count = np.unique(s, return_index=True,
                                        return_counts=True)
        x[shell] = 0.0
        for _ in range(n_iters):
            sums = np.add.reduceat(x[d], starts, axis=0) if len(d) else 0.0
            x[rows] = sums / count[:, None]
    return x
