"""Quickstart: the paper in 60 lines.

Build a columnar table -> dictionary-encode (Table 2) -> attach ADVs
(Tables 4/5) -> featurize via gathers -> train a Wide&Deep classifier on
device -> write the learned embedding back into the dictionary (Fig 2).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.columnar import Table
from repro.compile_cache import enable_compile_cache
from repro.core import FeatureSet, FeaturePipeline
from repro.core.feedback import store_embedding, rank_features
from repro.models.widedeep import (WideDeepConfig, init_widedeep,
                                   make_widedeep_train_step)

enable_compile_cache()
rng = np.random.default_rng(0)
N = 20_000

# 1. raw data -> columnar, dictionary-encoded storage ------------------------
states = np.array([f"State_{i:02d}" for i in range(50)])
raw = {
    "age": rng.integers(18, 90, N),
    "state": states[rng.integers(0, 50, N)],
    "income": rng.integers(20, 250, N) * 1000,
}
table = Table.from_data(raw)
print(table.summary())

# 2. featurization as ADVs (computed once on K dictionary rows) --------------
features = (FeatureSet()
            .add("age", "zscore")
            .add("age", "bucketize", boundaries=(30.0, 45.0, 65.0))
            .add("income", "minmax")
            .add("income", "log"))
pipe = FeaturePipeline(table, features)
print(f"deep feature dim: {pipe.out_dim}; "
      f"batch bytes ADV path: {pipe.bytes_moved_adv(1024)} "
      f"vs f32 path: {pipe.bytes_moved_recompute(1024)}")

# 3. label + Wide&Deep model ---------------------------------------------------
age, income = raw["age"], raw["income"]
y = ((age > 45) & (income > 90_000)).astype(np.float32)
state_codes = table["state"].codes()
cfg = WideDeepConfig(wide_cards=(50,), deep_dim=pipe.out_dim,
                     embed_cols=((50, 8),), hidden=(32, 16))
params = init_widedeep(cfg, jax.random.PRNGKey(0))
step = make_widedeep_train_step(cfg, lr=0.2)

losses = []
for i in range(600):
    idx = rng.integers(0, N, 512)
    deep = pipe.batch(idx)                                # ADV gather
    wide = jnp.asarray(state_codes[idx])[None, :]
    emb = [jnp.asarray(state_codes[idx])]
    params, loss = step(params, wide, deep, jnp.asarray(y[idx]), emb)
    losses.append(float(loss))
final = float(np.mean(losses[-20:]))
print(f"wide&deep loss: {losses[0]:.4f} -> {final:.4f}")
# better than the base-rate entropy floor (~0.66) and clearly descending
assert final < 0.55 and final < 0.65 * losses[0]

# 4. analytics cycle (paper §7): learned artifacts back into the dictionary ---
aug_state = pipe.augmented.get("state")
if aug_state is None:
    from repro.core import AugmentedDictionary
    aug_state = AugmentedDictionary(table["state"].dictionary)
store_embedding(aug_state, "emb.v1", np.asarray(params["embeds"][0]),
                analysis="quickstart-run")
print(aug_state.summary())
print("feature ranking:",
      rank_features({"deep": np.asarray(pipe.batch(np.arange(64))),
                     "wide": np.asarray(params["wide"])})[:2])
print("OK")
