"""The analytics CYCLE (paper §7, Fig 2): learn -> write back -> reuse.

Round 1 trains a model on hand-designed ADV features of a column whose true
structure is hidden (a scrambled categorical where the label depends on a
latent grouping). The trained per-code embedding is then distilled into a
*learned bucketization* written back into the dictionary (the 'ML G1' column
of Table 5). Round 2 trains a smaller model on the learned ADV and matches /
beats round 1 — the feedback loop paying off.

Run:  PYTHONPATH=src python examples/analytics_cycle.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.columnar import Dictionary
from repro.compile_cache import enable_compile_cache
from repro.core import AugmentedDictionary
from repro.core.feedback import learn_bucketization, store_embedding
from repro.models.widedeep import (WideDeepConfig, init_widedeep,
                                   make_widedeep_train_step)

enable_compile_cache()
rng = np.random.default_rng(7)
N, K, LATENT = 30_000, 200, 4

# hidden structure: each of 200 codes belongs to one of 4 latent groups
latent_group = rng.integers(0, LATENT, K)
codes_raw = rng.integers(0, K, N)
y = (latent_group[codes_raw] >= 2).astype(np.float32)
noise = rng.random(N) < 0.1
y = np.where(noise, 1 - y, y)

d, codes = Dictionary.from_data(codes_raw)
aug = AugmentedDictionary(d)
aug.add("hash8", "hash_bucket", n_buckets=8)          # round-1 guess feature


def train(deep_fn, embed_card, steps=400, dim=8, lr=0.15, seed=0):
    cfg = WideDeepConfig(wide_cards=(), deep_dim=deep_fn(codes[:1]).shape[1],
                         embed_cols=((embed_card, dim),) if embed_card else (),
                         hidden=(16,))
    params = init_widedeep(cfg, jax.random.PRNGKey(seed))
    step = make_widedeep_train_step(cfg, lr=lr)
    r = np.random.default_rng(seed)
    wide = jnp.zeros((0, 512), jnp.int32)
    losses = []
    for i in range(steps):
        idx = r.integers(0, N, 512)
        deep = jnp.asarray(deep_fn(codes[idx]))
        emb = [jnp.asarray(codes[idx])] if embed_card else None
        params, loss = step(params, wide, deep, jnp.asarray(y[idx]), emb)
        losses.append(float(loss))
    return params, losses


# ---- round 1: hash feature + per-code embedding -----------------------------
print("round 1: hash bucketization + learned embedding")
p1, l1 = train(lambda c: aug.featurize("hash8", c), embed_card=K,
               steps=800, lr=0.25)
print(f"  loss {l1[0]:.4f} -> {np.mean(l1[-20:]):.4f}")

# ---- feedback: distill the MODEL's per-code score into a bucketization -------
# score_k = round-1 model logit when shown dictionary code k (the 'average
# predicted logit per code' of core/feedback.py)
from repro.models.widedeep import forward_widedeep
emb = np.asarray(p1["embeds"][0])                     # (K, dim)
store_embedding(aug, "emb.round1", emb, analysis="round1")
all_codes = np.arange(K, dtype=np.int32)
cfg1 = WideDeepConfig(wide_cards=(), deep_dim=1, embed_cols=((K, 8),),
                      hidden=(16,))
scores = np.asarray(forward_widedeep(
    cfg1, p1, jnp.zeros((0, K), jnp.int32),
    jnp.asarray(aug.featurize("hash8", all_codes)),
    [jnp.asarray(all_codes)]))
learn_bucketization(aug, "ml_g1", scores, n_buckets=LATENT,
                    analysis="round1-distilled")
print("  wrote back ADVs:", sorted(aug.advs))

# purity of the learned buckets vs the DECISION-RELEVANT grouping: the label
# exposes only the binary split latent_group >= 2, so that is what a learned
# bucketization can (and should) recover.
buckets = aug["ml_g1"].table[:, 0].astype(int)
# align latent groups to DICTIONARY code order (codes are load-order indices)
binary_group = (latent_group[d.values.astype(int)] >= 2).astype(int)
purity = 0.0
for b in range(LATENT):
    mask = buckets == b
    if mask.sum():
        purity += max(np.bincount(binary_group[mask], minlength=2)) / K
print(f"  learned-bucket purity vs decision grouping: {purity:.2f}")

# ---- round 2: NO embedding, just the learned bucketization as one-hot --------
print("round 2: learned-ADV one-hot only (no embedding table)")
onehot = np.eye(LATENT, dtype=np.float32)


def deep2(c):
    return onehot[aug.featurize("ml_g1", c)[:, 0].astype(int)]


p2, l2 = train(deep2, embed_card=0, steps=400)
print(f"  loss {l2[0]:.4f} -> {np.mean(l2[-20:]):.4f}")

r1, r2 = np.mean(l1[-20:]), np.mean(l2[-20:])
print(f"\nanalytics cycle: round2 ({r2:.4f}) vs round1 ({r1:.4f}) "
      f"with {K}x{8} fewer feature params")
assert r2 < r1 * 1.2, "learned ADV should retain round-1 quality"
assert purity > 0.75, "learned bucketization should recover latent groups"
print("OK")
