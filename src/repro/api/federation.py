"""`Federation` — the one run facade over the unified engine.

``Federation.from_spec(spec)`` compiles a declarative
:class:`~repro.api.spec.FederationSpec` into a fully-wired
:class:`~repro.core.engine.FederationEngine` (synthetic corpus,
partitioned clients, loss/init — ProdLDA for ``model.family="ntm"``,
any registry LM architecture for ``model.family="lm"``
(docs/lm_federation.md), configs) and drives it with the
EXACT per-round seed schedule ``FederationEngine.fit`` has always used
(``seed * 100003 + round_idx``) — so a spec-built run retraces the
legacy ``RoundEngine``/CLI-flag wiring bit for bit (pinned in
tests/test_api_federation.py).

Lifecycle:

    fed = Federation.from_spec(spec)          # or a registry name / dict
    fed.on_round_end(lambda rec: ...)         # metric-stream hooks
    rec = fed.step()                          # one incremental round
    fed.run()                                 # to schedule.rounds (or
                                              # the rel_tol stop)
    state = fed.state_dict()                  # FULL engine snapshot
    fed2 = Federation.from_spec(spec)
    fed2.load_state_dict(state)               # resume: bit-identical
    fed.evaluate()                            # held-out ppl/NPMI/TSS

The snapshot covers *everything* round ``r+1`` depends on — params,
server-optimizer state, transform state (top-k error memories), the
straggler ring buffer / pending list, and the round counter; since the
cohort schedule, straggler draws and transform keys are pure functions
of ``(config, round_idx)``, a resumed run is indistinguishable from an
uninterrupted one (``examples/resume_demo.py`` asserts it bitwise).

Custom federations plug in through ``from_spec``'s keyword overrides
(``clients=``, ``loss_fn=``/``loss_sum_fn=``, ``init_params=``,
``corpus=``): the spec stays the single scenario description, the data
and objective come from the caller.
"""
from __future__ import annotations

import pickle
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, \
    Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.spec import FederationSpec, atomic_write
from repro.configs.base import ModelConfig
from repro.core.engine import ClientState, FederationEngine
from repro.core.ntm import prodlda
from repro.data.federated_split import parse_partition_spec, partition_corpus
from repro.data.lm_data import LMCorpus, generate_lm_corpus, lm_client_data
from repro.data.synthetic_lda import generate_lda_corpus
from repro.metrics import npmi_coherence, tss

Pytree = Any


def max_param_dev(a: Pytree, b: Pytree) -> float:
    """Max abs leafwise deviation between two param pytrees — the
    loop==vmap / resume acceptance metric used by the benchmarks and
    demos (the test suite keeps its own independent copy in conftest so
    the metric isn't checked against itself)."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        raise ValueError(f"pytrees have {len(la)} vs {len(lb)} leaves — "
                         "a truncating zip would hide missing params")
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# spec -> data wiring (the single home; launch/simulate.py re-exports)
# ---------------------------------------------------------------------------
def build_corpus(spec: FederationSpec):
    """The synthetic LDA federation a spec's ``data`` section describes."""
    return generate_lda_corpus(
        vocab_size=spec.model.vocab, num_topics=spec.model.topics,
        num_nodes=spec.data.num_clients,
        shared_topics=spec.resolved_shared_topics,
        docs_per_node=spec.data.docs_per_node,
        val_docs_per_node=spec.data.val_docs_per_node,
        seed=spec.resolved_data_seed)


def build_clients(syn, num_clients: int, partition: str,
                  seed: int = 0) -> List[ClientState]:
    """Turn the synthetic federation into ClientStates per the partition
    spec: ``topic`` keeps the paper's natural per-node topic split; any
    other registry spec pools the nodes' corpora and re-partitions the
    documents (labels = each document's dominant ground-truth topic)."""
    name, _ = parse_partition_spec(partition)
    if name in ("topic", "by_label"):
        return [ClientState(data={"bow": b}, num_docs=len(b))
                for b in syn.node_bows]
    bows = syn.concat_bows()
    labels = np.concatenate(syn.node_thetas).argmax(axis=1)
    parts = partition_corpus(len(bows), num_clients, partition,
                             labels=labels, seed=seed)
    if any(len(p) == 0 for p in parts):
        raise ValueError(f"partition {partition!r} left a client with no "
                         "documents; raise alpha or shrink num_clients")
    return [ClientState(data={"bow": bows[p]}, num_docs=len(p))
            for p in parts]


def build_lm_corpus(spec: FederationSpec) -> LMCorpus:
    """The synthetic federated token corpus a ``model.family='lm'``
    spec's ``data`` section describes (docs = fixed-length sequences)."""
    return generate_lm_corpus(
        vocab_size=spec.model.vocab, num_nodes=spec.data.num_clients,
        docs_per_node=spec.data.docs_per_node,
        seq_len=spec.resolved_seq_len,
        val_docs_per_node=spec.data.val_docs_per_node,
        seed=spec.resolved_data_seed)


def build_lm_clients(corpus: LMCorpus, num_clients: int, partition: str,
                     seed: int = 0) -> List[ClientState]:
    """:func:`build_clients` for token corpora: ``topic`` keeps the
    natural per-node vocabulary-window split; any other registry spec
    pools the documents and re-partitions them with origin-node labels
    (the token analogue of dominant-topic labels)."""
    name, _ = parse_partition_spec(partition)
    if name in ("topic", "by_label"):
        return [ClientState(data=lm_client_data(t), num_docs=len(t))
                for t in corpus.node_tokens]
    toks = corpus.concat_tokens()
    labels = np.concatenate([np.full(len(t), node)
                             for node, t in enumerate(corpus.node_tokens)])
    parts = partition_corpus(len(toks), num_clients, partition,
                             labels=labels, seed=seed)
    if any(len(p) == 0 for p in parts):
        raise ValueError(f"partition {partition!r} left a client with no "
                         "documents; raise alpha or shrink num_clients")
    return [ClientState(data=lm_client_data(toks[p]), num_docs=len(p))
            for p in parts]


def heldout_elbo_per_token(params, cfg: ModelConfig, val_bows: np.ndarray,
                           batch: int = 256) -> float:
    """Negative ELBO per held-out token (log perplexity bound)."""
    tot_elbo, tot_tokens = 0.0, 0.0
    for i in range(0, len(val_bows), batch):
        b = {"bow": jnp.asarray(val_bows[i:i + batch])}
        s, _ = prodlda.elbo_loss_sum(params, cfg, b, train=False)
        tot_elbo += float(s)
        tot_tokens += float(val_bows[i:i + batch].sum())
    return tot_elbo / max(tot_tokens, 1.0)


def heldout_perplexity(params, cfg: ModelConfig, val_bows: np.ndarray,
                       batch: int = 256) -> float:
    """exp(negative ELBO per held-out token) — the NTM perplexity bound.

    May legitimately overflow to ``inf`` for badly-fit models; the
    log-space :func:`heldout_elbo_per_token` is always finite."""
    with np.errstate(over="ignore"):
        return float(np.exp(heldout_elbo_per_token(params, cfg, val_bows,
                                                   batch)))


def heldout_xent_per_token(params, cfg: ModelConfig, val_tokens: np.ndarray,
                           batch: int = 256) -> float:
    """Mean next-token cross-entropy (nats) on held-out documents — the
    LM analogue of :func:`heldout_elbo_per_token` (pure CE even for MoE
    archs: the router aux is a training regularizer, not model quality).
    """
    from repro.models import transformer as tfm
    tot, n_tot = 0.0, 0.0
    for i in range(0, len(val_tokens), batch):
        t = jnp.asarray(val_tokens[i:i + batch])
        logits, _ = tfm.forward_train(params, cfg, {"tokens": t[:, :-1]})
        s, n = tfm.xent_loss(logits, t[:, 1:])
        tot += float(s)
        n_tot += float(n)
    return tot / max(n_tot, 1.0)


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------
class Federation:
    """One running federated scenario (module docstring).

    Construct via :meth:`from_spec`; the raw engine stays reachable as
    ``.engine`` for callers that need the stage-level surface
    (schedulers, trace counts, benchmarks)."""

    def __init__(self, spec: FederationSpec, engine: FederationEngine, *,
                 model_cfg: Optional[ModelConfig] = None, corpus=None):
        self.spec = spec
        self.engine = engine
        self.model_cfg = model_cfg
        self.corpus = corpus
        self._hooks: List[Callable[[Dict[str, float]], None]] = []

    # -- construction -----------------------------------------------------
    @classmethod
    def from_spec(cls, spec: Union[FederationSpec, Mapping, str], *,
                  corpus=None, clients: Optional[Sequence[ClientState]] = None,
                  loss_fn=None, loss_sum_fn=None,
                  init_params: Optional[Pytree] = None) -> "Federation":
        """Compile a spec (object, ``to_dict`` mapping, or registry
        scenario name) into a wired, runnable federation.

        ``corpus``/``clients``/``loss_fn``/``init_params`` override the
        synthetic defaults — pass a prebuilt corpus to share it across
        cells (the benchmarks do), or explicit clients + objective to
        run the spec's *scenario* over your own federation."""
        if isinstance(spec, str):
            from repro.api.registry import scenario_spec
            spec = scenario_spec(spec)
        elif isinstance(spec, Mapping):
            spec = FederationSpec.from_dict(spec)
        spec.validate()
        if spec.schedule.mode == "buffered_async":
            raise ValueError(
                "schedule.mode='buffered_async' describes the "
                "long-running federation service, not a "
                "round-synchronous simulation — build it with "
                "repro.serve.FederationService.from_spec(spec) "
                "(docs/serving.md); Federation runs sync specs only")
        cfg = spec.to_model_config()
        if spec.model.family == "lm":
            corpus, clients, loss_fn, loss_sum_fn, init_params = \
                cls._wire_lm(spec, cfg, corpus, clients, loss_fn,
                             loss_sum_fn, init_params)
            engine = FederationEngine(
                loss_fn, init_params, clients, spec.to_federated_config(),
                spec.to_round_config(),
                batch_size=spec.execution.batch_size,
                loss_sum_fn=loss_sum_fn, message="delta")
            return cls(spec, engine, model_cfg=cfg, corpus=corpus)
        if clients is None:
            if corpus is None:
                corpus = build_corpus(spec)
            elif len(corpus.node_bows) != spec.data.num_clients:
                raise ValueError(
                    f"injected corpus has {len(corpus.node_bows)} nodes "
                    f"but the spec declares data.num_clients="
                    f"{spec.data.num_clients}")
            else:
                got = tuple(np.shape(corpus.beta))
                want = (spec.model.topics, spec.model.vocab)
                if got != want:
                    raise ValueError(
                        f"injected corpus was generated for (topics, "
                        f"vocab)={got} but the spec declares {want} — "
                        "a mismatched corpus would only fail later as "
                        "an opaque shape error inside the jitted loss")
            clients = build_clients(corpus, spec.data.num_clients,
                                    spec.data.partition.to_string(),
                                    seed=spec.resolved_data_seed)
        if loss_fn is None:
            train = spec.execution.stochastic_loss
            loss_fn = lambda p, b: prodlda.elbo_loss(  # noqa: E731
                p, cfg, b, train=train)
            if loss_sum_fn is None:
                # the (sum, count) form is mask-aware — it lets the vmap
                # path keep zero-padded rows out of the objective for
                # ragged federations
                loss_sum_fn = lambda p, b: prodlda.elbo_loss_sum(  # noqa: E731,E501
                    p, cfg, b, train=train)
        if init_params is None:
            init_params = prodlda.init_params(
                jax.random.PRNGKey(spec.execution.seed), cfg)
        engine = FederationEngine(
            loss_fn, init_params, clients, spec.to_federated_config(),
            spec.to_round_config(), batch_size=spec.execution.batch_size,
            loss_sum_fn=loss_sum_fn, message="delta")
        return cls(spec, engine, model_cfg=cfg, corpus=corpus)

    @staticmethod
    def _wire_lm(spec, cfg, corpus, clients, loss_fn, loss_sum_fn,
                 init_params):
        """``model.family='lm'`` wiring: registry model bundle + token
        corpus, same override surface as the NTM path."""
        from repro.models.registry import build_model
        # a published model computes as its config states (bf16
        # activations over fp32 parameters); the CPU presets in fp32
        bundle = build_model(cfg, dtype=None if spec.model.published
                             else jnp.float32)
        if clients is None:
            if corpus is None:
                corpus = build_lm_corpus(spec)
            else:
                if not isinstance(corpus, LMCorpus):
                    raise ValueError(
                        "model.family='lm' needs an LMCorpus (use "
                        "repro.data.lm_data.generate_lm_corpus), got "
                        f"{type(corpus).__name__}")
                if corpus.num_nodes != spec.data.num_clients:
                    raise ValueError(
                        f"injected corpus has {corpus.num_nodes} nodes "
                        f"but the spec declares data.num_clients="
                        f"{spec.data.num_clients}")
                got = (corpus.vocab_size, corpus.seq_len)
                want = (spec.model.vocab, spec.resolved_seq_len)
                if got != want:
                    raise ValueError(
                        f"injected corpus was generated for (vocab, "
                        f"seq_len)={got} but the spec declares {want} — "
                        "a mismatched corpus would only fail later as "
                        "an opaque shape error inside the jitted loss")
            clients = build_lm_clients(corpus, spec.data.num_clients,
                                       spec.data.partition.to_string(),
                                       seed=spec.resolved_data_seed)
        if loss_fn is None:
            loss_fn = bundle.loss
            if loss_sum_fn is None:
                # (sum, count): mask-aware, so zero-padded cohort rows
                # stay out of the fused vmap objective
                loss_sum_fn = bundle.loss_sum
        if init_params is None:
            init_params = bundle.init(
                jax.random.PRNGKey(spec.execution.seed))
        return corpus, clients, loss_fn, loss_sum_fn, init_params

    # -- state ------------------------------------------------------------
    @property
    def params(self) -> Pytree:
        return self.engine.params

    @property
    def history(self) -> List[Dict[str, float]]:
        return self.engine.history

    @property
    def round_index(self) -> int:
        """Rounds completed so far (== the next round's index)."""
        return self.engine._round

    @property
    def mesh_shape(self) -> Optional[Dict[str, int]]:
        """The engine's RESOLVED device-mesh axes (``{"data": N}``), or
        None when running unsharded — what ``execution.mesh`` actually
        compiled to (loop mode: always None, the mesh knob is inert
        there).  Benchmarks record this per cell next to
        ``device_count``."""
        mesh = getattr(self.engine, "_mesh", None)
        return dict(mesh.shape) if mesh is not None else None

    # -- stepping ---------------------------------------------------------
    def _round_seed(self, round_idx: int) -> int:
        # the fixed schedule FederationEngine.fit has always used —
        # trajectory-comparable across presets, exec modes and resumes
        return self.spec.execution.seed * 100003 + round_idx

    def on_round_end(self, fn: Callable[[Dict[str, float]], None]):
        """Register a metric-stream hook called with every completed
        round's record; returns ``fn`` (decorator-friendly)."""
        self._hooks.append(fn)
        return fn

    def step(self) -> Dict[str, float]:
        """Run exactly one round; fire hooks; return the round record."""
        rec = self.engine.round(seed=self._round_seed(self.engine._round))
        for fn in self._hooks:
            fn(rec)
        return rec

    def run(self, rounds: Optional[int] = None, *,
            verbose: bool = False) -> Pytree:
        """Step until ``schedule.rounds`` total rounds have run
        (``rounds=N`` runs at most N MORE rounds instead), honoring the
        engine's rel-tol stopping criterion — on a fresh federation this
        is step-for-step ``FederationEngine.fit``."""
        total = self.spec.schedule.rounds if rounds is None \
            else self.engine._round + rounds
        while self.engine._round < total:
            rec = self.step()
            if verbose and rec["round"] % 10 == 0:
                print(f"[round {rec['round']:4d}] loss={rec['loss']:.4f} "
                      f"rel={rec['rel_change']:.2e} "
                      f"K={rec['participants']} "
                      f"arrived={rec['arrived']}")
            if self.engine.stop_criterion(rec, self.engine.fed.rel_tol):
                break
        return self.engine.params

    # -- snapshot / resume -------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        """Host-side snapshot: the spec (identity check on load) + the
        FULL engine state (``FederationEngine.state_dict``)."""
        return {"spec": self.spec.to_dict(),
                "engine": self.engine.state_dict()}

    def load_state_dict(self, state: Mapping[str, Any]) -> None:
        """Restore a :meth:`state_dict` snapshot.  The snapshot must have
        been taken under THIS spec — the resume contract is "same spec,
        same trajectory", so a drifted spec is refused, not reinterpreted.
        """
        snap_spec = state.get("spec")
        if snap_spec is not None and snap_spec != self.spec.to_dict():
            raise ValueError(
                "snapshot spec does not match this Federation's spec — "
                "resume requires Federation.from_spec with the SAME spec "
                "the snapshot was taken under (diff the two to_dict() "
                "trees to see what changed)")
        self.engine.load_state_dict(state["engine"])

    def save_state(self, path: str) -> str:
        """Atomic pickle of :meth:`state_dict` (numpy + primitives only).
        Pickle is a trusted-input format: only load files you wrote."""
        state = self.state_dict()
        return atomic_write(path, lambda f: pickle.dump(state, f),
                            binary=True)

    def load_state(self, path: str) -> None:
        with open(path, "rb") as f:
            self.load_state_dict(pickle.load(f))

    # -- evaluation --------------------------------------------------------
    def evaluate(self, *, batch: int = 256) -> Dict[str, float]:
        """Held-out quality against the generative ground truth (the
        metric block ``simulate.py`` has always reported).  NTM
        federations get the paper's ELBO/perplexity/NPMI/TSS block; LM
        federations get held-out next-token cross-entropy + perplexity.
        """
        if self.corpus is None or self.model_cfg is None:
            raise ValueError(
                "evaluate() needs the synthetic corpus and model config; "
                "this Federation was built over injected clients — score "
                "params with repro.metrics directly instead")
        if isinstance(self.corpus, LMCorpus):
            if not len(self.corpus.val_tokens):
                raise ValueError(
                    "evaluate() needs held-out documents; set "
                    "data.val_docs_per_node > 0 in the spec")
            xent = heldout_xent_per_token(
                self.engine.params, self.model_cfg,
                self.corpus.val_tokens, batch)
            with np.errstate(over="ignore"):
                ppl = float(np.exp(xent))
            return {"heldout_xent_per_token": xent,
                    "heldout_perplexity": ppl}
        val = self.corpus.concat_val_bows()
        params = self.engine.params
        beta = np.asarray(prodlda.get_topics(params))
        # one held-out ELBO pass; perplexity is exp() of it (recomputing
        # via heldout_perplexity would double the validation forwards)
        elbo = heldout_elbo_per_token(params, self.model_cfg, val, batch)
        with np.errstate(over="ignore"):
            ppl = float(np.exp(elbo))
        return {
            "heldout_elbo_per_token": elbo,
            "heldout_perplexity": ppl,
            "npmi_coherence": float(npmi_coherence(beta, val)),
            "tss": float(tss(self.corpus.beta, beta)),
        }
