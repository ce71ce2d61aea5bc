"""KG-embedding decoders (scoring functions) — paper §2.1 Eq. 4.

The paper trains DistMult (``g(s,r,t) = h_s^T M_r h_t`` with diagonal M_r)
and states its scaling approach is "agnostic to the used knowledge graph
embedding model" (§6).  This module makes that agnosticism structural: every
decoder is a registered :class:`Decoder` whose load-bearing contract is the
**canonical query form**

    ``prepare_query(params, h_s, rel)      -> (q, q_bias)``      (B, d), (B,)
    ``prepare_candidates(params, C)        -> (C', c_bias)``   (..., d), (...)
    ``scores = epilogue(q @ C'^T + q_bias[:, None] + c_bias)``

with two epilogue families (``repro.kernels.kge_score.EPILOGUES``):

* ``bilinear`` — identity; DistMult and ComplEx reduce to a plain matmul
  (``q_bias = c_bias = 0``).
* ``neg_l2``   — ``-sqrt(max(x, 0) + NORM_EPS)`` (safe norm, eps under the
  sqrt).  TransE and RotatE use the norm-expansion trick
  ``‖u − c‖² = ‖u‖² + ‖c‖² − 2 u·c``: ``prepare_query`` folds the ``−2``
  into the query (``q = −2u``, ``q_bias = ‖u‖²``) and ``prepare_candidates``
  carries ``c_bias = ‖c‖²`` — the candidate matrix itself is untouched, so
  row-sharded entity tables need no per-decoder transform.

Because both families reduce to one matmul plus rank-1 biases, a single
Pallas kernel (``repro.kernels.kge_score``), the candidate-axis-sharded
ranking path (``repro.eval.sharded``) and the serving engine
(``repro.serving.KGEServer``) carry EVERY registered decoder.  Both
epilogues are elementwise and deterministic, so per-shard greater/equal tie
counts match the dense reference exactly — sharded == dense stays ``==``
for every decoder (``tests/test_decoders.py``).

``Decoder.score`` (the training/direct form) is DEFINED through the same
prepare functions and epilogue, so direct and candidate-form scores use the
identical stabilization — there is no second formula to drift (the old
``transe_score`` added ``1e-9`` inside the difference vector, shifting every
score; the safe-norm epilogue replaces it).  Precision note: the expansion
cancels catastrophically once ``‖u − c‖²`` falls within float32 rounding of
``‖u‖² + ‖c‖²`` (distances ≲1e-3 at typical norms), where scores clamp to
the ``-sqrt(NORM_EPS)`` floor with zero gradient — the accepted cost of
keeping ranking one matmul and direct == candidate scores bit-consistent
(a direct-subtraction ``score()`` would be more accurate there but a
DIFFERENT function from what ranking computes).

String names (CLI / config back-compat) resolve through :func:`get_decoder`;
no ``if name == "distmult"`` dispatch exists outside this registry.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels.kge_score import EPILOGUES, apply_epilogue


# ====================================================================== #
# The Decoder protocol + registry
# ====================================================================== #
@dataclasses.dataclass(frozen=True)
class Decoder:
    """Base class: a registered scoring function in canonical query form.

    Subclasses define ``init_params`` / ``prepare_query`` /
    ``prepare_candidates`` and declare their ``epilogue`` family; ``score``
    and ``score_candidates`` are derived, so every execution path (training
    triplet scoring, dense ranking, sharded ranking, serving top-k) computes
    the same function.  Instances are stateless frozen singletons — safe as
    jit-static closure constants.
    """

    name: str = ""
    epilogue: str = "bilinear"

    def __post_init__(self):
        if self.epilogue not in EPILOGUES:
            raise ValueError(f"unknown epilogue {self.epilogue!r}")

    # ---- per-decoder surface -------------------------------------------
    def init_params(self, key: jax.Array, num_relations: int,
                    dim: int) -> Dict[str, jax.Array]:
        raise NotImplementedError

    def prepare_query(self, params, h_s: jax.Array,
                      rel: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(B, d) heads + (B,) relation ids → query rows ``q`` (B, d) and
        pre-epilogue bias ``q_bias`` (B,)."""
        raise NotImplementedError

    def prepare_candidates(self, params,
                           candidates: jax.Array
                           ) -> Tuple[jax.Array, jax.Array]:
        """(..., d) candidate tails → ``(C', c_bias)`` with matching leading
        dims.  Must be row-local (each output row a function of its input
        row only) so per-shard candidate blocks prepare independently and
        bitwise-match the dense preparation."""
        raise NotImplementedError

    # ---- derived: every path is the query form -------------------------
    def score(self, params, h_s: jax.Array, rel: jax.Array,
              h_t: jax.Array) -> jax.Array:
        """(B,) triplet scores — the row-wise query form (training path)."""
        q, q_bias = self.prepare_query(params, h_s, rel)
        c, c_bias = self.prepare_candidates(params, h_t)
        return apply_epilogue(jnp.sum(q * c, axis=-1) + q_bias + c_bias,
                              self.epilogue)

    def score_candidates(self, params, h_s: jax.Array, rel: jax.Array,
                         candidates: jax.Array,
                         bias: Optional[jax.Array] = None) -> jax.Array:
        """(B, C) rank-evaluation scores, pure-XLA path (the oracle the
        Pallas kernel is checked against; ``bias`` is the post-epilogue
        filter mask)."""
        q, q_bias = self.prepare_query(params, h_s, rel)
        c, c_bias = self.prepare_candidates(params, candidates)
        scores = apply_epilogue(
            q @ c.T + q_bias[:, None] + c_bias[None, :], self.epilogue)
        return scores if bias is None else scores + bias

    def rank_scores(self, params, h_s: jax.Array, rel: jax.Array,
                    candidates: jax.Array,
                    bias: Optional[jax.Array] = None, *,
                    prepared: Optional[Tuple[jax.Array, jax.Array]] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
        """(B, C) rank-evaluation scores through the Pallas kernel
        (``kernels.ops.kge_score_padded``).  ``prepared`` short-circuits
        ``prepare_candidates`` with a cached ``(C', c_bias)`` — callers that
        rank many query batches against one candidate set (dense ranking,
        serving) prepare once."""
        from repro.kernels.ops import kge_score_padded
        q, q_bias = self.prepare_query(params, h_s, rel)
        if prepared is None:
            prepared = self.prepare_candidates(params, candidates)
        c, c_bias = prepared
        return kge_score_padded(q, c, bias, q_bias, c_bias,
                                epilogue=self.epilogue, interpret=interpret)


_REGISTRY: Dict[str, Decoder] = {}


def register_decoder(decoder: Decoder) -> Decoder:
    """Add a Decoder singleton to the registry (idempotent per name)."""
    if not decoder.name:
        raise ValueError("decoder needs a name")
    _REGISTRY[decoder.name] = decoder
    return decoder


def get_decoder(decoder: Union[str, Decoder]) -> Decoder:
    """Resolve a decoder name (CLI/config strings) or pass through an
    instance — the ONLY string-to-decoder dispatch point in the system."""
    if isinstance(decoder, Decoder):
        return decoder
    try:
        return _REGISTRY[decoder]
    except KeyError:
        raise ValueError(
            f"unknown decoder {decoder!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def registered_decoders() -> Tuple[str, ...]:
    """Registered decoder names, sorted — drives parametrized tests and the
    per-decoder benchmark sweeps."""
    return tuple(sorted(_REGISTRY))


# ====================================================================== #
# The paper's decoders + RotatE (extensibility proof)
# ====================================================================== #
def _split_complex(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """First-half/second-half re/im convention shared by ComplEx and
    RotatE."""
    d = x.shape[-1] // 2
    return x[..., :d], x[..., d:]


def _neg_l2_query(u: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Norm-expansion query: ``q = −2u``, ``q_bias = ‖u‖²`` so that
    ``q·c + q_bias + c_bias = ‖u − c‖²`` (pre-epilogue)."""
    return -2.0 * u, jnp.sum(u * u, axis=-1)


def _zeros_bias(x: jax.Array) -> jax.Array:
    return jnp.zeros(x.shape[:-1], x.dtype)


@dataclasses.dataclass(frozen=True)
class DistMult(Decoder):
    """``g = h_s^T diag(m_r) h_t`` — Eq. 4 with diagonal M_r."""

    name: str = "distmult"
    epilogue: str = "bilinear"

    def init_params(self, key, num_relations, dim):
        return {"rel_diag": jax.random.normal(key, (num_relations, dim))
                * (1.0 / jnp.sqrt(dim))}

    def prepare_query(self, params, h_s, rel):
        q = h_s * params["rel_diag"][rel]
        return q, _zeros_bias(q)

    def prepare_candidates(self, params, candidates):
        return candidates, _zeros_bias(candidates)


@dataclasses.dataclass(frozen=True)
class TransE(Decoder):
    """``g = −‖h_s + r − h_t‖₂`` via the norm expansion (safe-norm: eps
    under the sqrt, NOT inside the difference vector)."""

    name: str = "transe"
    epilogue: str = "neg_l2"

    def init_params(self, key, num_relations, dim):
        return {"rel_vec": jax.random.normal(key, (num_relations, dim))
                * (1.0 / jnp.sqrt(dim))}

    def prepare_query(self, params, h_s, rel):
        return _neg_l2_query(h_s + params["rel_vec"][rel])

    def prepare_candidates(self, params, candidates):
        return candidates, jnp.sum(candidates * candidates, axis=-1)


@dataclasses.dataclass(frozen=True)
class ComplEx(Decoder):
    """``g = Re(<h_s, r, conj(h_t)>)`` with first/second-half re/im: the
    relation-rotated query ``q = (s_r r_r − s_i r_i, s_r r_i + s_i r_r)``
    makes it a plain real matmul against untouched candidates."""

    name: str = "complex"
    epilogue: str = "bilinear"

    def init_params(self, key, num_relations, dim):
        if dim % 2:
            raise ValueError("ComplEx needs even dim")
        return {"rel_complex": jax.random.normal(key, (num_relations, dim))
                * (1.0 / jnp.sqrt(dim))}

    def prepare_query(self, params, h_s, rel):
        sr, si = _split_complex(h_s)
        rr, ri = _split_complex(params["rel_complex"][rel])
        q = jnp.concatenate([sr * rr - si * ri, sr * ri + si * rr], axis=-1)
        return q, _zeros_bias(q)

    def prepare_candidates(self, params, candidates):
        return candidates, _zeros_bias(candidates)


@dataclasses.dataclass(frozen=True)
class RotatE(Decoder):
    """``g = −‖h_s ∘ r − h_t‖₂`` with unit-modulus relations
    ``r = e^{iθ_r}`` (sun et al. 2019), L2 form: the phase rotation of the
    head is the query, candidates ride the same neg_l2 norm expansion as
    TransE.  Registered to prove the query-form protocol extends past the
    paper's decoder set without touching kernel/eval/serving code."""

    name: str = "rotate"
    epilogue: str = "neg_l2"

    def init_params(self, key, num_relations, dim):
        if dim % 2:
            raise ValueError("RotatE needs even dim")
        return {"rel_phase": jax.random.uniform(
            key, (num_relations, dim // 2),
            minval=-jnp.pi, maxval=jnp.pi)}

    def prepare_query(self, params, h_s, rel):
        hr, hi = _split_complex(h_s)
        theta = params["rel_phase"][rel]
        cos, sin = jnp.cos(theta), jnp.sin(theta)
        u = jnp.concatenate([hr * cos - hi * sin, hr * sin + hi * cos],
                            axis=-1)
        return _neg_l2_query(u)

    def prepare_candidates(self, params, candidates):
        return candidates, jnp.sum(candidates * candidates, axis=-1)


DISTMULT = register_decoder(DistMult())
TRANSE = register_decoder(TransE())
COMPLEX = register_decoder(ComplEx())
ROTATE = register_decoder(RotatE())


# ====================================================================== #
# Functional conveniences (all registry-resolved)
# ====================================================================== #
def init_decoder_params(key: jax.Array, decoder: Union[str, Decoder],
                        num_relations: int, dim: int) -> Dict[str, jax.Array]:
    return get_decoder(decoder).init_params(key, num_relations, dim)


def score_triplets(params, decoder: Union[str, Decoder], h: jax.Array,
                   triplets: jax.Array) -> jax.Array:
    """Score (T, 3) batch-local triplets against vertex states h (V, d)."""
    dec = get_decoder(decoder)
    return dec.score(params, h[triplets[:, 0]], triplets[:, 1],
                     h[triplets[:, 2]])


def score_against_candidates(
    params, decoder: Union[str, Decoder], h_s: jax.Array, rel: jax.Array,
    candidates: jax.Array, bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Rank-evaluation form: (B, d) heads × (C, d) candidate tails →
    (B, C), pure-XLA.  The Pallas twin is ``Decoder.rank_scores``."""
    return get_decoder(decoder).score_candidates(params, h_s, rel,
                                                 candidates, bias)


def pairwise_sum(x: jax.Array) -> jax.Array:
    """Sum over the leading axis in one fixed order: adjacent pairs first,
    level by level, an odd last element carried up a level.

    ``jnp.sum`` leaves the order to the compiler, which on TPU picks it
    per program: the same values summed inside an 8-trainer and a
    4-trainer vmapped step can round differently.  Elementwise adds in a
    fixed tree leave it no choice.  A contiguous, aligned block of a
    power-of-two number of elements is a subtree of this order, so summing
    such blocks first and then their sums gives the same bits as summing
    everything at once — the property the spmd and simulated train steps
    share (``repro.training.distributed``)."""
    while x.shape[0] > 1:
        n = x.shape[0] - x.shape[0] % 2
        pairs = x[0:n:2] + x[1:n:2]
        x = pairs if n == x.shape[0] else jnp.concatenate([pairs, x[n:]])
    return x[0]


def bce_loss(scores: jax.Array, labels: jax.Array,
             mask: jax.Array) -> jax.Array:
    """Paper Eq. 3: mean binary cross-entropy over positives+negatives,
    numerically stable logits form, padding masked out, summed in
    ``pairwise_sum``'s order so the value does not depend on the program
    it is compiled into."""
    per = jnp.maximum(scores, 0) - scores * labels + \
        jnp.log1p(jnp.exp(-jnp.abs(scores)))
    denom = jnp.maximum(mask.sum(), 1.0)
    return pairwise_sum(per * mask) / denom
