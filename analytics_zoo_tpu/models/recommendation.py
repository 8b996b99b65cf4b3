"""Recommendation models: NeuralCF, WideAndDeep, SessionRecommender.

Architectures follow the reference exactly:
- NeuralCF (`models/recommendation/NeuralCF.scala:60-97`, py
  `neuralcf.py:30`): dual MLP embeddings concat → Dense relu stack, optional
  GMF branch (mf embeddings multiplied) concatenated before the softmax.
- WideAndDeep (`WideAndDeep.scala`, py `wide_and_deep.py:140-180`): wide
  linear over sparse-ish wide features + deep MLP over
  indicator/embedding/continuous columns, summed then softmax.
- SessionRecommender (`session_recommender.py:69-94`): GRU stack over session
  item embeddings, optional history MLP branch, summed logits → softmax.

The reference's inputs use 1-based ids (Embedding tables sized count+1);
kept here. On TPU the embedding lookups become gathers feeding fused MXU
matmuls; one jit program per model.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.keras import Input, Model
from analytics_zoo_tpu.keras import layers as L
from analytics_zoo_tpu.models.common import ZooModel


class UserItemFeature:
    """(user_id, item_id, label) record used by the recommender helpers
    (`pyzoo/zoo/models/recommendation/utils.py`)."""

    def __init__(self, user_id: int, item_id: int, label: int = 0):
        self.user_id, self.item_id, self.label = user_id, item_id, label


class Recommender(ZooModel):
    """Shared ranking helpers (`Recommender` in
    `pyzoo/zoo/models/recommendation/__init__.py`)."""

    def predict_user_item_pair(self, features: Sequence[UserItemFeature],
                               batch_per_thread: int = 32) -> np.ndarray:
        x = np.array([[f.user_id, f.item_id] for f in features], np.int32)
        return self.predict(x, batch_per_thread=batch_per_thread)

    def recommend_for_user(self, features: Sequence[UserItemFeature],
                           max_items: int = 5):
        """Top-N items per user from candidate pairs."""
        probs = self.predict_user_item_pair(features)
        score = probs[:, -1] if probs.ndim > 1 else probs
        by_user = {}
        for f, s in zip(features, score):
            by_user.setdefault(f.user_id, []).append((f.item_id, float(s)))
        return {u: sorted(items, key=lambda t: -t[1])[:max_items]
                for u, items in by_user.items()}

    def recommend_for_item(self, features: Sequence[UserItemFeature],
                           max_users: int = 5):
        probs = self.predict_user_item_pair(features)
        score = probs[:, -1] if probs.ndim > 1 else probs
        by_item = {}
        for f, s in zip(features, score):
            by_item.setdefault(f.item_id, []).append((f.user_id, float(s)))
        return {i: sorted(users, key=lambda t: -t[1])[:max_users]
                for i, users in by_item.items()}


class NeuralCF(Recommender):
    """Neural Collaborative Filtering (`NeuralCF.scala:60`)."""

    def __init__(self, user_count: int, item_count: int, class_num: int,
                 user_embed: int = 20, item_embed: int = 20,
                 hidden_layers: Sequence[int] = (40, 20, 10),
                 include_mf: bool = True, mf_embed: int = 20):
        super().__init__()
        self._config = dict(user_count=user_count, item_count=item_count,
                            class_num=class_num, user_embed=user_embed,
                            item_embed=item_embed,
                            hidden_layers=list(hidden_layers),
                            include_mf=include_mf, mf_embed=mf_embed)
        self.user_count, self.item_count = user_count, item_count
        self.class_num = class_num
        self.user_embed, self.item_embed = user_embed, item_embed
        self.hidden_layers = list(hidden_layers)
        self.include_mf, self.mf_embed = include_mf, mf_embed
        self.model = self.build_model()

    def build_model(self) -> Model:
        # input: [B, 2] of (user_id, item_id) — `neuralcf.py:55-57`
        inp = Input(shape=(2,))

        def under(scope, layer):
            layer.scope = scope     # names the program's part, no more
            return layer
        user = under("ncf/embeddings", L.Select(1, 0))(inp)
        item = under("ncf/embeddings", L.Select(1, 1))(inp)

        def table(name, count, width, ids):
            return under("ncf/embeddings", L.Flatten())(
                under("ncf/embeddings", L.Embedding(
                    count + 1, width, init="uniform", name=name))(ids))
        mlp_user = table("ncf_mlp_user", self.user_count, self.user_embed,
                         user)
        mlp_item = table("ncf_mlp_item", self.item_count, self.item_embed,
                         item)
        x = under("ncf/mlp", L.Merge(mode="concat"))([mlp_user, mlp_item])
        for units in self.hidden_layers:
            x = under("ncf/mlp", L.Dense(units, activation="relu"))(x)
        table_names = ["ncf_mlp_user", "ncf_mlp_item"]
        if self.include_mf:
            assert self.mf_embed > 0
            mf_user = table("ncf_mf_user", self.user_count, self.mf_embed,
                            user)
            mf_item = table("ncf_mf_item", self.item_count, self.mf_embed,
                            item)
            gmf = under("ncf/gmf", L.Merge(mode="mul"))([mf_user, mf_item])
            x = under("ncf/head", L.Merge(mode="concat"))([x, gmf])
            table_names += ["ncf_mf_user", "ncf_mf_item"]
        out = under("ncf/head",
                    L.Dense(self.class_num, activation="softmax"))(x)
        model = Model(inp, out)

        # Declare the embedding tables for the lazy row-sparse optimizer
        # path (`learn/lazy_embedding.py`; Estimator.fit
        # lazy_embeddings=True): the dense Adam sweep over these tables
        # is ~78% of device step time at MovieLens scale.
        import jax.numpy as jnp
        col = {"ncf_mlp_user": 0, "ncf_mlp_item": 1,
               "ncf_mf_user": 0, "ncf_mf_item": 1}

        def ids_fn(c):
            return lambda xb: jnp.asarray(xb[..., c], jnp.int32)

        def set_ids_fn(c):
            # write twin for the fused sparse backward (segment_update):
            # rewrite the id column so the model's gather reads
            # positions 0..B into a pre-gathered rows array instead of
            # vocabulary ids (B < 2^24, exact in the f32 input)
            return lambda xb, ids: xb.at[..., c].set(
                ids.astype(xb.dtype))

        from analytics_zoo_tpu.learn.lazy_embedding import LazyEmbeddingSpec
        model.lazy_embedding_specs = [
            LazyEmbeddingSpec((n, "embeddings"), ids_fn(col[n]),
                              set_ids_fn=set_ids_fn(col[n]))
            for n in table_names]
        return model


class WideAndDeep(Recommender):
    """Wide & Deep (`wide_and_deep.py:94,140-180`). Inputs (by model_type):
    wide [B, wide_dims], indicator [B, sum(indicator_dims)], embed ids
    [B, len(embed_in_dims)], continuous [B, len(continuous_cols)]."""

    def __init__(self, class_num: int, model_type: str = "wide_n_deep",
                 wide_base_dims: Sequence[int] = (),
                 wide_cross_dims: Sequence[int] = (),
                 indicator_dims: Sequence[int] = (),
                 embed_in_dims: Sequence[int] = (),
                 embed_out_dims: Sequence[int] = (),
                 continuous_cols: Sequence[str] = (),
                 hidden_layers: Sequence[int] = (40, 20, 10)):
        super().__init__()
        self._config = dict(class_num=class_num, model_type=model_type,
                            wide_base_dims=list(wide_base_dims),
                            wide_cross_dims=list(wide_cross_dims),
                            indicator_dims=list(indicator_dims),
                            embed_in_dims=list(embed_in_dims),
                            embed_out_dims=list(embed_out_dims),
                            continuous_cols=list(continuous_cols),
                            hidden_layers=list(hidden_layers))
        self.class_num = class_num
        self.model_type = model_type
        self.wide_dims = sum(wide_base_dims) + sum(wide_cross_dims)
        self.indicator_dims = list(indicator_dims)
        self.embed_in_dims = list(embed_in_dims)
        self.embed_out_dims = list(embed_out_dims)
        self.continuous_cols = list(continuous_cols)
        self.hidden_layers = list(hidden_layers)
        self.model = self.build_model()

    def _deep_branch(self):
        inputs, merged = [], []
        if self.indicator_dims:
            ind = Input(shape=(sum(self.indicator_dims),))
            inputs.append(ind)
            merged.append(ind)
        if self.embed_in_dims:
            emb_in = Input(shape=(len(self.embed_in_dims),))
            inputs.append(emb_in)
            for i, (vin, vout) in enumerate(zip(self.embed_in_dims,
                                                self.embed_out_dims)):
                col = L.Select(1, i)(emb_in)
                merged.append(L.Flatten()(
                    L.Embedding(vin + 1, vout, init="uniform")(col)))
        if self.continuous_cols:
            con = Input(shape=(len(self.continuous_cols),))
            inputs.append(con)
            merged.append(con)
        x = merged[0] if len(merged) == 1 else L.merge(merged, mode="concat")
        for units in self.hidden_layers:
            x = L.Dense(units, activation="relu")(x)
        # reference ends the deep tower with a relu Dense to class_num
        # (`wide_and_deep.py:179`)
        out = L.Dense(self.class_num, activation="relu")(x)
        return inputs, out

    def build_model(self) -> Model:
        if self.model_type == "wide":
            wide = Input(shape=(self.wide_dims,))
            out = L.Activation("softmax")(L.Dense(self.class_num)(wide))
            return Model(wide, out)
        if self.model_type == "deep":
            inputs, deep = self._deep_branch()
            out = L.Activation("softmax")(deep)
            return Model(inputs if len(inputs) > 1 else inputs[0], out)
        if self.model_type == "wide_n_deep":
            wide = Input(shape=(self.wide_dims,))
            wide_linear = L.Dense(self.class_num)(wide)
            inputs, deep = self._deep_branch()
            merged = L.merge([wide_linear, deep], mode="sum")
            out = L.Activation("softmax")(merged)
            return Model([wide] + inputs, out)
        raise TypeError(f"Unsupported model_type: {self.model_type}")


class SessionRecommender(Recommender):
    """Session-based GRU recommender (`session_recommender.py:30,69-94`)."""

    def __init__(self, item_count: int, item_embed: int = 100,
                 rnn_hidden_layers: Sequence[int] = (40, 20),
                 session_length: int = 0, include_history: bool = False,
                 mlp_hidden_layers: Sequence[int] = (40, 20),
                 history_length: int = 0):
        super().__init__()
        if session_length <= 0:
            raise ValueError("session_length must be positive")
        if include_history and history_length <= 0:
            raise ValueError("history_length must be positive with history")
        self._config = dict(item_count=item_count, item_embed=item_embed,
                            rnn_hidden_layers=list(rnn_hidden_layers),
                            session_length=session_length,
                            include_history=include_history,
                            mlp_hidden_layers=list(mlp_hidden_layers),
                            history_length=history_length)
        self.item_count = item_count
        self.item_embed = item_embed
        self.rnn_hidden_layers = list(rnn_hidden_layers)
        self.session_length = session_length
        self.include_history = include_history
        self.mlp_hidden_layers = list(mlp_hidden_layers)
        self.history_length = history_length
        self.model = self.build_model()

    def build_model(self) -> Model:
        inp_rnn = Input(shape=(self.session_length,))
        x = L.Embedding(self.item_count + 1, self.item_embed,
                        init="uniform")(inp_rnn)
        for units in self.rnn_hidden_layers[:-1]:
            x = L.GRU(units, return_sequences=True)(x)
        x = L.GRU(self.rnn_hidden_layers[-1], return_sequences=False)(x)
        rnn_logits = L.Dense(self.item_count)(x)
        if self.include_history:
            inp_mlp = Input(shape=(self.history_length,))
            h = L.Embedding(self.item_count + 1, self.item_embed,
                            init="uniform")(inp_mlp)
            from analytics_zoo_tpu.ops.autograd import Lambda
            import jax.numpy as jnp
            h = Lambda(lambda t: jnp.sum(t, axis=1))(h)
            for units in self.mlp_hidden_layers:
                h = L.Dense(units, activation="relu")(h)
            mlp_logits = L.Dense(self.item_count)(h)
            merged = L.merge([rnn_logits, mlp_logits], mode="sum")
            out = L.Activation("softmax")(merged)
            return Model([inp_rnn, inp_mlp], out)
        out = L.Activation("softmax")(rnn_logits)
        return Model(inp_rnn, out)

    def recommend_for_session(self, sessions: np.ndarray, max_items: int = 5,
                              zero_based_label: bool = True):
        probs = self.predict(sessions)
        top = np.argsort(-probs, axis=-1)[:, :max_items]
        shift = 0 if zero_based_label else 1
        return [list(zip((t + shift).tolist(), probs[i, t].tolist()))
                for i, t in enumerate(top)]
