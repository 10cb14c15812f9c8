"""`FederationSpec` — the declarative, serializable scenario tree.

Every federated scenario this repo can express (the paper's Algorithm-1
regime and every beyond-paper composition of partitioner x participation
x staleness x heterogeneity x transforms x server optimizer x execution
mode) is describable as ONE versioned dataclass tree:

    FederationSpec
      ├── model        what the federation trains (ProdLDA, or any
      │                registry LM family — docs/lm_federation.md)
      ├── data         synthetic federation + partition sub-spec
      │     └── partition   registry partitioner (kind + alpha)
      ├── schedule     rounds, participation, staleness, heterogeneity
      ├── transforms   message privacy/compression stage (dp/topk/secure)
      ├── server_opt   server-side update rule on the combined delta
      └── execution    exec mode, batch, client lr, seeds, stopping

The tree is the single source of truth three consumers compile from:

  * :class:`repro.api.federation.Federation` — the run facade
    (``Federation.from_spec(spec).run()``);
  * ``launch/simulate.py`` — legacy CLI flags compile into a spec
    (``spec_from_args``), ``--spec file.json`` loads one verbatim;
  * ``benchmarks/bench_scenarios.py`` / ``bench_clients.py`` — cells are
    named registry scenarios (``repro.api.registry``) over a sized base
    spec.

Specs VALIDATE at construction (``__post_init__``): every field is
range-checked and cross-section incoherences (a declared ``dp``
transform without noise, ``secure`` under stragglers, privacy knobs
without a declared transform stage) raise ``ValueError`` with an
actionable message — the same refusals ``core/engine.py`` enforces,
surfaced before any corpus is built.

Serialization contract (pinned by tests/test_api_spec.py and the CI
``spec-validate`` step):

    FederationSpec.from_dict(spec.to_dict()) == spec
    FederationSpec.from_json(spec.to_json()) == spec

``to_dict`` emits plain JSON types (tuples become lists); ``from_dict``
is STRICT — unknown sections or keys and unsupported ``version`` values
raise instead of being silently dropped, so a typo in a spec file can
never quietly run the wrong scenario.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from dataclasses import dataclass, field
from typing import Any, Dict, Mapping, Optional, Tuple

from repro.configs.base import NTM, FederatedConfig, ModelConfig, RoundConfig
from repro.core.aggregation import SERVER_OPTIMIZERS
from repro.core.engine import EXEC_MODES, KERNEL_BACKENDS, RoundScheduler
from repro.core.transforms import TRANSFORMS
from repro.data.federated_split import parse_partition_spec

SPEC_VERSION = 1

# schedule.mode values: "sync" = round-synchronous simulation
# (Federation); "buffered_async" = the long-running FedBuff-style
# service (repro.serve.FederationService, docs/serving.md)
SCHEDULE_MODES = ("sync", "buffered_async")
# staleness-discount policies for buffered-async aggregation: the
# discount scales the DELTA, never the Eq. (2) weight (DESIGN.md §6)
STALENESS_POLICIES = ("exponential", "polynomial")
# delta payload formats on the repro.net wire (serving.wire_precision):
# bf16 halves upload bytes with the `precision` transform's cast rule
WIRE_PRECISIONS = ("fp32", "bf16")


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(f"invalid FederationSpec: {msg}")


# the process umask, probed ONCE at import (single-threaded): toggling
# it per write would briefly zero the process-wide umask under threads
_UMASK = os.umask(0)
os.umask(_UMASK)


def atomic_write(path: str, writer, *, binary: bool = False) -> str:
    """Atomic file write (tmp + rename): ``writer(f)`` fills the file.

    The single home for the spec/snapshot write discipline —
    ``FederationSpec.save`` and ``Federation.save_state`` both go
    through here, so a durability fix lands in one place.
    """
    dirname = os.path.dirname(path) or "."
    os.makedirs(dirname, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".tmp")
    try:
        # mkstemp files are 0600; match what a plain open() would have
        # created so dumped specs/snapshots stay shareable
        os.chmod(tmp, 0o666 & ~_UMASK)
        with os.fdopen(fd, "wb" if binary else "w") as f:
            writer(f)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path


def parse_int_tuple(s, *, what: str = "int list",
                    minimum: int = 0) -> Tuple[int, ...]:
    """Parse a comma-separated int list STRICTLY (the CLI front-door).

    Unlike the pre-redesign ``_int_tuple`` — which silently dropped
    empty elements, so ``--hetero-epochs 1,,4`` trained a different
    schedule than the user wrote — every malformed or out-of-range
    element raises ``ValueError`` naming the offending position:

    >>> parse_int_tuple("1,2,4")
    (1, 2, 4)
    >>> parse_int_tuple("")
    ()

    ``what`` names the flag/field in the error message; ``minimum``
    rejects values below it (epochs schedules pass ``minimum=1``).
    """
    if s is None:
        return ()
    if isinstance(s, (tuple, list)):
        out = []
        for i, x in enumerate(s):
            if isinstance(x, bool) or not isinstance(x, int):
                raise ValueError(f"{what}: {x!r} at position {i} is not "
                                 "an integer")
            if x < minimum:
                raise ValueError(
                    f"{what}: {x} at position {i} is out of range "
                    f"(must be >= {minimum})")
            out.append(x)
        return tuple(out)
    toks = str(s).split(",")
    if len(toks) == 1 and not toks[0].strip():
        return ()
    out = []
    for pos, tok in enumerate(toks):
        t = tok.strip()
        if not t:
            raise ValueError(
                f"{what}: empty element at position {pos} in {s!r} — "
                "write an explicit integer for every comma-separated "
                "slot (e.g. '1,2,4'); elements are never silently "
                "dropped")
        try:
            v = int(t)
        except ValueError:
            raise ValueError(
                f"{what}: {t!r} at position {pos} in {s!r} is not an "
                "integer") from None
        if v < minimum:
            raise ValueError(
                f"{what}: {v} at position {pos} in {s!r} is out of "
                f"range (must be >= {minimum})")
        out.append(v)
    return tuple(out)


def _check_int(v, where: str, minimum: int, *,
               allow_none: bool = False) -> None:
    """Scalar int field check: TYPE first (floats/bools would validate
    on the range check alone, then crash or misbehave far from the
    spec — 'rounds': 5.5 runs range() wrong, 'vocab': 64.5 dies inside
    jax init), then range."""
    if v is None and allow_none:
        return
    _require(isinstance(v, int) and not isinstance(v, bool),
             f"{where} must be an int, got {v!r}")
    _require(v >= minimum, f"{where} must be >= {minimum}, got {v}")


def _check_float(v, where: str, minimum: Optional[float] = None,
                 maximum: Optional[float] = None, *,
                 exclusive_min: bool = False) -> None:
    """Float field check: TYPE first — a JSON string like '0.5' would
    otherwise escape the range comparison as a raw TypeError with no
    spec context.  Ints are acceptable float values; bools are not."""
    _require(isinstance(v, (int, float)) and not isinstance(v, bool),
             f"{where} must be a number, got {v!r}")
    if minimum is not None:
        if exclusive_min:
            _require(v > minimum, f"{where} must be > {minimum}, got {v}")
        else:
            _require(v >= minimum,
                     f"{where} must be >= {minimum}, got {v}")
    if maximum is not None:
        _require(v <= maximum, f"{where} must be <= {maximum}, got {v}")


def _check_bool(v, where: str) -> None:
    """Bool field check: the JSON string "false" is truthy — accepting
    it would silently run the wrong scenario."""
    _require(isinstance(v, bool), f"{where} must be true/false, got "
                                  f"{v!r}")


def _check_int_tuple(v, where: str, minimum: int = 0) -> None:
    _require(isinstance(v, tuple),
             f"{where} must be a tuple/list of ints, got "
             f"{type(v).__name__}")
    for i, x in enumerate(v):
        _require(isinstance(x, int) and not isinstance(x, bool),
                 f"{where}[{i}] must be an int, got {x!r}")
        _require(x >= minimum,
                 f"{where}[{i}] must be >= {minimum}, got {x}")


# ---------------------------------------------------------------------------
# sections
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ModelSpec:
    """``model`` section: what the federation trains.

    Two families share the section (docs/lm_federation.md):

    * ``family="ntm"`` (default) — the paper's ProdLDA topic model;
      ``vocab``/``topics``/``hidden`` size it, the LM-only fields must
      stay at their zero defaults.
    * ``family="lm"`` — a language model from the architecture registry
      (``repro.configs.ARCHS``), resolved through ``models/registry.py``
      over the arch's ``reduced()`` config.  ``arch`` picks the family
      (dense/moe/ssm/hybrid — the audio and vision-language archs need
      modality batch keys the federated token pipeline does not carry);
      ``layers``/``width``/``seq_len`` override the reduced sizing
      (``0`` = keep the reduced default), and the NTM-only
      ``topics``/``hidden`` must stay at their defaults — fields are
      never silently dropped.

      ``published=True`` starts from the arch's published config instead,
      at every width, and states one chip's share of a deployment:
      ``layers`` (the pipeline stage's depth), ``ep_size`` (the experts
      split over that many chips, this one holding the first share) and
      ``vocab`` (the vocabulary slice, ids ``0..vocab-1``); ``width`` is
      refused there.
    """
    family: str = "ntm"
    vocab: int = 400
    topics: int = 10
    hidden: int = 64            # both encoder MLP widths
    # -- LM-only fields (family="lm") -----------------------------------
    arch: str = ""              # repro.configs.ARCHS id
    layers: int = 0             # 0 = the arch's reduced() layer count
    width: int = 0              # d_model override; 0 = reduced default
    seq_len: int = 0            # tokens per document; 0 = 32
    published: bool = False     # the published widths, not reduced()
    ep_size: int = 0            # chips sharing each MoE layer; 0 = all held

    def _validate(self) -> None:
        _require(self.family in ("ntm", "lm"),
                 f"model.family {self.family!r} is not one of "
                 "('ntm', 'lm')")
        _check_int(self.vocab, "model.vocab", 2)
        _check_int(self.topics, "model.topics", 1)
        _check_int(self.hidden, "model.hidden", 1)
        _require(isinstance(self.arch, str),
                 f"model.arch must be a string, got {self.arch!r}")
        _check_int(self.layers, "model.layers", 0)
        _check_int(self.width, "model.width", 0)
        _check_int(self.seq_len, "model.seq_len", 0)
        _check_bool(self.published, "model.published")
        _check_int(self.ep_size, "model.ep_size", 0)
        if self.family == "ntm":
            _require(self.arch == "" and self.layers == 0
                     and self.width == 0 and self.seq_len == 0
                     and not self.published and self.ep_size == 0,
                     "model.arch/layers/width/seq_len/published/ep_size "
                     "are LM-only fields — set model.family='lm' to use "
                     "them; fields are never silently dropped")
            return
        # family == "lm"
        from repro.configs import ARCHS
        from repro.configs.base import AUDIO, NTM, VLM
        _require(self.arch in ARCHS,
                 f"model.arch {self.arch!r} is not a registered "
                 f"architecture; known: {sorted(ARCHS)}")
        kind = ARCHS[self.arch].kind
        _require(kind not in (NTM, AUDIO, VLM),
                 f"model.arch {self.arch!r} has kind {kind!r} — "
                 "model.family='lm' federates the token-causal "
                 "families (dense/moe/ssm/hybrid); audio and "
                 "vision-language archs need modality batch keys the "
                 "federated token pipeline does not carry, and NTM "
                 "archs go through model.family='ntm'")
        # matching the class defaults: the NTM shape fields have no LM
        # meaning, so a non-default value would be silently dropped
        _require(self.topics == 10 and self.hidden == 64,
                 "model.topics/model.hidden are NTM-only fields — "
                 "leave them at their defaults under model.family='lm'; "
                 "fields are never silently dropped")
        if self.width:
            _require(self.width % 64 == 0,
                     f"model.width must be a multiple of 64 (the "
                     f"federated LM head size), got {self.width}")
        if self.seq_len:
            _require(self.seq_len >= 2,
                     f"model.seq_len must be >= 2, got {self.seq_len}")
        if not self.published:
            _require(self.ep_size == 0,
                     "model.ep_size states a published model's expert "
                     "share — set model.published=True; fields are never "
                     "silently dropped")
            return
        cfg = ARCHS[self.arch]
        _require(self.width == 0,
                 "model.width resizes a reduced() preset; a published "
                 "model keeps every published width")
        _require(self.vocab <= cfg.vocab_size,
                 f"model.vocab={self.vocab} is not a slice of "
                 f"{self.arch}'s {cfg.vocab_size}-id vocabulary")
        _require(self.layers == 0
                 or cfg.first_k_dense < self.layers <= cfg.num_layers,
                 f"model.layers={self.layers} must keep every leading "
                 f"dense layer ({cfg.first_k_dense}) and one more, and at "
                 f"most {self.arch}'s {cfg.num_layers}")
        if self.ep_size:
            _require(cfg.moe.routing == "noaux_tc"
                     and cfg.moe.num_experts % self.ep_size == 0,
                     f"model.ep_size={self.ep_size} must divide "
                     f"{self.arch}'s routed experts, under the held-expert "
                     "layer (noaux_tc routing)")


@dataclass(frozen=True)
class PartitionSpec:
    """``data.partition`` sub-section: registry partitioner + alpha.

    Serializes as ``{"kind": ..., "alpha": ...}`` but also accepts the
    CLI's string form (``"dirichlet(0.3)"``) anywhere a partition value
    appears; ``alpha=None`` means the partitioner's default.
    """
    kind: str = "topic"
    alpha: Optional[float] = None

    @classmethod
    def from_value(cls, v, where: str = "data.partition") -> "PartitionSpec":
        if isinstance(v, cls):
            return v
        if isinstance(v, str):
            name, kw = parse_partition_spec(v)
            return cls(kind=name, alpha=kw.get("alpha"))
        if isinstance(v, Mapping):
            unknown = sorted(set(v) - {"kind", "alpha"})
            if unknown:
                raise ValueError(f"unknown key(s) {unknown} in {where}; "
                                 "known: ['alpha', 'kind']")
            return cls(kind=v.get("kind", "topic"), alpha=v.get("alpha"))
        raise ValueError(
            f"{where} must be a partition spec string (e.g. "
            f"'dirichlet(0.3)') or a {{kind, alpha}} mapping, got "
            f"{type(v).__name__}")

    def to_string(self) -> str:
        """The canonical CLI/`RoundConfig.partition` string form."""
        if self.alpha is None:
            return self.kind
        return f"{self.kind}({self.alpha!r})"

    def _validate(self) -> None:
        # round-trip through the canonical parser: validates the kind
        # against the registry, parametric-vs-not, and alpha > 0 —
        # one set of error messages for the CLI and the spec
        parse_partition_spec(self.to_string())


@dataclass(frozen=True)
class DataSpec:
    """``data`` section: the synthetic LDA federation + its partition."""
    num_clients: int = 5
    docs_per_node: int = 400
    val_docs_per_node: int = 80
    # None -> max(model.topics // 5, 1), the historical simulate default
    shared_topics: Optional[int] = None
    # None -> execution.seed (the CLI's one-seed-everywhere convention)
    seed: Optional[int] = None
    partition: PartitionSpec = field(default_factory=PartitionSpec)

    def _validate(self) -> None:
        _check_int(self.num_clients, "data.num_clients", 1)
        _check_int(self.docs_per_node, "data.docs_per_node", 1)
        _check_int(self.val_docs_per_node, "data.val_docs_per_node", 0)
        _check_int(self.shared_topics, "data.shared_topics", 0,
                   allow_none=True)
        # numpy's default_rng (corpus build, partitioners) rejects
        # negative seeds — catch it here, not deep in corpus build
        _check_int(self.seed, "data.seed", 0, allow_none=True)
        _require(isinstance(self.partition, PartitionSpec),
                 "data.partition must be a PartitionSpec (or the string/"
                 "mapping forms accepted by from_dict)")
        self.partition._validate()


@dataclass(frozen=True)
class ScheduleSpec:
    """``schedule`` section: rounds, participation, staleness,
    heterogeneity, availability — the `RoundConfig` regime surface."""
    rounds: int = 100
    clients_per_round: int = 0          # 0 = all clients (paper Alg. 1)
    sampling: str = "uniform"
    # None -> execution.seed
    sampling_seed: Optional[int] = None
    local_epochs: int = 1
    local_epochs_by_client: Tuple[int, ...] = ()
    client_join_round: Tuple[int, ...] = ()
    client_leave_round: Tuple[int, ...] = ()
    straggler_prob: float = 0.0
    max_staleness: int = 0
    staleness_decay: float = 0.5
    # ---- buffered-async service knobs (docs/serving.md) --------------
    # mode="buffered_async" describes the long-running FederationService
    # (repro.serve): aggregation fires whenever `buffer_size` client
    # deltas accumulate — no round barrier.  Under it, max_staleness is
    # the version-lag acceptance bound and staleness_policy picks the
    # delta discount.  Sync specs must leave these at their defaults:
    # async knobs are never silently dropped.
    mode: str = "sync"
    buffer_size: int = 0                # M; 0 = the cohort width K
    staleness_policy: str = ""          # "" -> "exponential" under async

    def _validate(self) -> None:
        _check_int(self.rounds, "schedule.rounds", 1)
        _check_int(self.clients_per_round, "schedule.clients_per_round",
                   0)
        # the scheduler seeds numpy RNGs: non-negative only
        _check_int(self.sampling_seed, "schedule.sampling_seed", 0,
                   allow_none=True)
        _require(self.sampling in RoundScheduler.MODES,
                 f"schedule.sampling {self.sampling!r} is not one of "
                 f"{RoundScheduler.MODES}")
        _check_int(self.local_epochs, "schedule.local_epochs", 1)
        _check_int_tuple(self.local_epochs_by_client,
                         "schedule.local_epochs_by_client", minimum=1)
        _check_int_tuple(self.client_join_round,
                         "schedule.client_join_round")
        _check_int_tuple(self.client_leave_round,
                         "schedule.client_leave_round")
        _check_float(self.straggler_prob, "schedule.straggler_prob",
                     0.0, 1.0)
        _check_int(self.max_staleness, "schedule.max_staleness", 0)
        # outside [0, 1] stale deltas are amplified or sign-flipped
        _check_float(self.staleness_decay, "schedule.staleness_decay",
                     0.0, 1.0)
        _require(self.mode in SCHEDULE_MODES,
                 f"schedule.mode {self.mode!r} is not one of "
                 f"{SCHEDULE_MODES}")
        _check_int(self.buffer_size, "schedule.buffer_size", 0)
        _require(self.staleness_policy in ("",) + STALENESS_POLICIES,
                 f"schedule.staleness_policy {self.staleness_policy!r} "
                 f"is not one of {STALENESS_POLICIES} (or '' for the "
                 "mode default)")
        if self.mode == "sync":
            _require(self.buffer_size == 0,
                     "schedule.buffer_size is a buffered-async knob but "
                     "schedule.mode is 'sync' — set "
                     "schedule.mode='buffered_async' (docs/serving.md); "
                     "async knobs are never silently dropped")
            _require(self.staleness_policy == "",
                     "schedule.staleness_policy is a buffered-async "
                     "knob but schedule.mode is 'sync' — set "
                     "schedule.mode='buffered_async' (docs/serving.md); "
                     "async knobs are never silently dropped")
        else:
            _require(self.straggler_prob == 0.0,
                     "schedule.straggler_prob simulates in-round delays "
                     "and needs a round barrier; under "
                     "schedule.mode='buffered_async' staleness is REAL "
                     "version lag (bounded by schedule.max_staleness) — "
                     "drop the straggler knob")


@dataclass(frozen=True)
class TransformsSpec:
    """``transforms`` section: the ordered message-transform stage."""
    names: Tuple[str, ...] = ()
    dp_noise_multiplier: float = 0.0
    dp_clip_norm: float = 1.0
    compression_topk: float = 0.0
    precision: str = ""             # "" = fp32 wire; "bf16" with 'precision'

    def _validate(self) -> None:
        _require(isinstance(self.names, tuple),
                 "transforms.names must be a tuple/list of transform "
                 "names")
        for n in self.names:
            _require(n in TRANSFORMS,
                     f"transforms.names entry {n!r} is not a registered "
                     f"transform; known: {sorted(TRANSFORMS)}")
        _check_float(self.dp_noise_multiplier,
                     "transforms.dp_noise_multiplier", 0.0)
        _check_float(self.dp_clip_norm, "transforms.dp_clip_norm", 0.0,
                     exclusive_min=True)
        _check_float(self.compression_topk, "transforms.compression_topk",
                     0.0, 1.0)
        # the never-silently-dropped contract, both directions (mirrors
        # the engine's construction-time refusals with spec-level words)
        if "dp" in self.names:
            _require(self.dp_noise_multiplier > 0,
                     "the 'dp' transform needs "
                     "transforms.dp_noise_multiplier > 0 — with zero "
                     "noise it would silently degrade to clip-only "
                     "while claiming local DP")
        elif self.dp_noise_multiplier > 0:
            _require(False,
                     "transforms.dp_noise_multiplier > 0 but 'dp' is "
                     "not in transforms.names — declare the stage "
                     "explicitly (names=('dp', ...)); privacy knobs are "
                     "never silently dropped")
        if "topk" in self.names:
            _require(self.compression_topk > 0,
                     "the 'topk' transform needs "
                     "transforms.compression_topk > 0")
        elif self.compression_topk > 0:
            _require(False,
                     "transforms.compression_topk > 0 but 'topk' is "
                     "not in transforms.names — declare the stage "
                     "explicitly (names=('topk', ...)); compression "
                     "knobs are never silently dropped")
        _require(self.precision in ("", "bf16"),
                 f"transforms.precision {self.precision!r} is not a "
                 "supported wire format; one of ('', 'bf16')")
        if "precision" in self.names:
            _require(self.precision == "bf16",
                     "the 'precision' transform needs "
                     "transforms.precision = 'bf16' (the only wire "
                     "format implemented) — an empty precision with the "
                     "stage enabled would silently be a no-op cast")
        elif self.precision:
            _require(False,
                     "transforms.precision is set but 'precision' is "
                     "not in transforms.names — declare the stage "
                     "explicitly (names=('precision', ...)); wire-format "
                     "knobs are never silently dropped")


@dataclass(frozen=True)
class ServerOptSpec:
    """``server_opt`` section: the rule applied to the combined delta."""
    name: str = "fedavg"
    lr: float = 1.0
    momentum: float = 0.9       # FedAvgM beta / FedAdam b1
    beta2: float = 0.999        # FedAdam b2
    eps: float = 1e-3           # FedAdam tau

    def _validate(self) -> None:
        _require(self.name in SERVER_OPTIMIZERS,
                 f"server_opt.name {self.name!r} is not a registered "
                 f"server optimizer; known: {sorted(SERVER_OPTIMIZERS)}")
        _check_float(self.lr, "server_opt.lr", 0.0, exclusive_min=True)
        _check_float(self.momentum, "server_opt.momentum", 0.0)
        _require(self.momentum < 1.0,
                 f"server_opt.momentum must be in [0, 1), got "
                 f"{self.momentum}")
        _check_float(self.beta2, "server_opt.beta2", 0.0,
                     exclusive_min=True)
        _require(self.beta2 < 1.0,
                 f"server_opt.beta2 must be in (0, 1), got {self.beta2}")
        _check_float(self.eps, "server_opt.eps", 0.0, exclusive_min=True)


@dataclass(frozen=True)
class MeshSpec:
    """``execution.mesh`` sub-section: the device-mesh axis shape.

    ``{"data": N}`` shards the fused vmap graphs' cohort axis — the
    stacked ``(K, ...)`` batches/deltas/weights, the ``(L, ...)`` top-k
    error-memory tree and the straggler ring — over the first ``N``
    local devices (a ``("data",)`` mesh built by
    :func:`repro.parallel.sharding.fed_mesh`).  ``None`` (the field
    default on :class:`ExecutionSpec`) is today's single-device
    behavior; ``data=1`` builds a real one-device mesh, i.e. the
    sharded code path without cross-device traffic.  Serializes as the
    ``{"data": N}`` mapping; ``from_value`` also accepts the CLI's
    ``"data=N"`` string form.
    """
    data: int = 1

    @classmethod
    def from_value(cls, v, where: str = "execution.mesh"):
        if v is None or isinstance(v, cls):
            return v
        if isinstance(v, str):
            axis, sep, size = v.partition("=")
            if axis.strip() != "data" or not sep:
                raise ValueError(f"{where} string form must be 'data=N', "
                                 f"got {v!r}")
            try:
                return cls(data=int(size))
            except ValueError:
                raise ValueError(f"{where}: axis size {size!r} is not an "
                                 "integer") from None
        if isinstance(v, Mapping):
            unknown = sorted(set(v) - {"data"})
            if unknown:
                raise ValueError(f"unknown key(s) {unknown} in {where}; "
                                 "known: ['data']")
            return cls(data=v.get("data", 1))
        raise ValueError(
            f"{where} must be null, a {{data: N}} mapping, or the "
            f"'data=N' string form, got {type(v).__name__}")

    def _validate(self) -> None:
        _check_int(self.data, "execution.mesh.data", 1)


@dataclass(frozen=True)
class ExecutionSpec:
    """``execution`` section: how (and how long) the spec runs."""
    exec_mode: str = "loop"
    batch_size: int = 64
    pad_cohorts: bool = True
    learning_rate: float = 2e-3     # client-side lambda of Eq. (3)
    rel_tol: float = 0.0            # 0 = run exactly schedule.rounds
    stochastic_loss: bool = False   # train-mode ELBO (dropout + reparam)
    seed: int = 0
    # aggregation kernel backend for the fused vmap graphs: "xla" (the
    # parity reference) | "pallas" (kernels/fed_aggregate.py).  Like
    # pad_cohorts, accepted-but-inert under exec_mode="loop" — the host
    # loop is itself the reference both vmap backends are held to.
    kernel_backend: str = "xla"
    # device-mesh shape for the fused vmap graphs (None = single
    # device).  Like kernel_backend, accepted-but-inert under
    # exec_mode="loop" — the host loop stays the unsharded reference
    # the sharded graphs are held to (so a cell's loop run never needs
    # the mesh's devices).
    mesh: Optional[MeshSpec] = None

    def _validate(self) -> None:
        _require(self.exec_mode in EXEC_MODES,
                 f"execution.exec_mode {self.exec_mode!r} is not one of "
                 f"{EXEC_MODES}")
        _require(self.kernel_backend in KERNEL_BACKENDS,
                 f"execution.kernel_backend {self.kernel_backend!r} is "
                 f"not one of {KERNEL_BACKENDS}")
        _check_int(self.batch_size, "execution.batch_size", 1)
        _check_bool(self.pad_cohorts, "execution.pad_cohorts")
        _check_bool(self.stochastic_loss, "execution.stochastic_loss")
        _check_float(self.learning_rate, "execution.learning_rate", 0.0,
                     exclusive_min=True)
        _check_float(self.rel_tol, "execution.rel_tol", 0.0)
        # feeds numpy RNGs (scheduler, straggler draws): non-negative
        _check_int(self.seed, "execution.seed", 0)
        _require(self.mesh is None or isinstance(self.mesh, MeshSpec),
                 "execution.mesh must be null or a MeshSpec (or the "
                 "mapping/string forms accepted by from_dict)")
        if self.mesh is not None:
            self.mesh._validate()


@dataclass(frozen=True)
class ServingSpec:
    """``serving`` section (optional): the repro.net wire front-end.

    Describes where the buffered-async service listens and what format
    delta uploads travel in (``repro.net``, docs/serving.md).  ``None``
    (the :class:`FederationSpec` default) means no wire — the service
    is driven in-process.  ``port=0`` binds an ephemeral port (the
    test/bench default; the bound port is reported by the server).
    ``wire_precision="bf16"`` halves upload payloads using the
    ``precision`` transform's cast rule (down to bfloat16 on encode,
    straight back to float32 on decode).  Only buffered-async specs may
    carry the section — a sync spec has no server, and the section is
    never silently dropped.
    """
    host: str = "127.0.0.1"
    port: int = 0
    wire_precision: str = "fp32"

    @classmethod
    def from_value(cls, v, where: str = "serving"):
        if v is None or isinstance(v, cls):
            return v
        if isinstance(v, Mapping):
            fields = {f.name for f in dataclasses.fields(cls)}
            unknown = sorted(set(v) - fields)
            if unknown:
                raise ValueError(f"unknown key(s) {unknown} in {where}; "
                                 f"known: {sorted(fields)}")
            return cls(**dict(v))
        raise ValueError(
            f"{where} must be null or a {{host, port, wire_precision}} "
            f"mapping, got {type(v).__name__}")

    def _validate(self) -> None:
        _require(isinstance(self.host, str) and self.host != "",
                 f"serving.host must be a non-empty string, got "
                 f"{self.host!r}")
        _check_int(self.port, "serving.port", 0)
        _require(self.port <= 65535,
                 f"serving.port must be <= 65535, got {self.port}")
        _require(self.wire_precision in WIRE_PRECISIONS,
                 f"serving.wire_precision {self.wire_precision!r} is not "
                 f"one of {WIRE_PRECISIONS}")


_SECTIONS = {
    "model": ModelSpec,
    "data": DataSpec,
    "schedule": ScheduleSpec,
    "transforms": TransformsSpec,
    "server_opt": ServerOptSpec,
    "execution": ExecutionSpec,
}


# ---------------------------------------------------------------------------
# the spec tree
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class FederationSpec:
    """One serializable federated scenario (module docstring).

    The all-defaults spec IS the paper regime: topic partition, full
    participation, E = 1, synchronous, FedAvg(server_lr=1) — i.e.
    Algorithm 1 (the ``"paper"`` registry scenario).  Validation runs at
    construction; every instance that exists is a runnable scenario.
    """
    version: int = SPEC_VERSION
    name: str = ""
    model: ModelSpec = field(default_factory=ModelSpec)
    data: DataSpec = field(default_factory=DataSpec)
    schedule: ScheduleSpec = field(default_factory=ScheduleSpec)
    transforms: TransformsSpec = field(default_factory=TransformsSpec)
    server_opt: ServerOptSpec = field(default_factory=ServerOptSpec)
    execution: ExecutionSpec = field(default_factory=ExecutionSpec)
    # optional wire front-end (repro.net); None = in-process only
    serving: Optional[ServingSpec] = None

    def __post_init__(self):
        self.validate()

    # -- validation -------------------------------------------------------
    def validate(self) -> None:
        """Range-check every section + refuse cross-section incoherence."""
        _require(isinstance(self.version, int)
                 and not isinstance(self.version, bool)
                 and self.version == SPEC_VERSION,
                 f"version {self.version!r} is not supported by this "
                 f"build (expected {SPEC_VERSION}); migrate the spec or "
                 "update the repo")
        _require(isinstance(self.name, str), "name must be a string")
        for sect, cls in _SECTIONS.items():
            v = getattr(self, sect)
            _require(isinstance(v, cls),
                     f"section {sect!r} must be a {cls.__name__}, got "
                     f"{type(v).__name__}")
            v._validate()
        _require(self.serving is None
                 or isinstance(self.serving, ServingSpec),
                 "section 'serving' must be null or a ServingSpec (or "
                 "the mapping form accepted by from_dict)")
        if self.serving is not None:
            self.serving._validate()
            _require(self.schedule.mode == "buffered_async",
                     "the serving section configures the repro.net wire "
                     "front-end of the buffered-async FederationService "
                     "(docs/serving.md) — a sync spec has no server; "
                     "remove the section (it is never silently dropped)")
        # cross-section coherence (mirrors core/engine.py refusals so a
        # bad spec fails at validation time, not engine-construction time)
        if self.model.family == "lm":
            _require(not self.execution.stochastic_loss,
                     "execution.stochastic_loss is the train-mode ELBO "
                     "(dropout + reparametrization) of the NTM family — "
                     "the federated LM objective is deterministic; drop "
                     "the flag under model.family='lm' instead of having "
                     "it silently ignored")
        if "secure" in self.transforms.names:
            self._refuse_secure_under_scan()
            _require("precision" not in self.transforms.names,
                     "the 'secure' transform is incompatible with "
                     "'precision' (bf16 messages): pairwise masks cancel "
                     "BITWISE only on the fp32 dyadic grid — rounding "
                     "masked messages to bfloat16 destroys the "
                     "cancellation, a silent privacy downgrade, never a "
                     "tolerable approximation")
            sch, L = self.schedule, self.data.num_clients
            _require(not (sch.straggler_prob > 0 and sch.max_staleness > 0),
                     "the 'secure' transform is incompatible with the "
                     "straggler buffer (schedule.straggler_prob/"
                     "max_staleness): a stale masked message arrives in "
                     "a later combine than its pair partners, so the "
                     "pairwise masks no longer cancel")
            k = sch.clients_per_round or L
            _require(min(k, L) >= L
                     and not any(j > 0 for j in sch.client_join_round)
                     and not any(x > 0 for x in sch.client_leave_round),
                     "the 'secure' transform needs synchronous full "
                     "participation (clients_per_round = 0 or "
                     "num_clients, no client join/leave): pairwise "
                     "masks only cancel when every client's message "
                     "joins the same combine")
        if self.schedule.mode == "buffered_async":
            L = self.data.num_clients
            m = self.resolved_buffer_size
            _require(m <= L,
                     f"schedule.buffer_size M={m} exceeds "
                     f"data.num_clients L={L} — the service holds at "
                     "most ONE in-flight delta per client (the newest "
                     "upload supersedes), so a buffer wider than the "
                     "population can never fill and aggregation would "
                     "never fire")
            _require("secure" not in self.transforms.names,
                     "the 'secure' transform is incompatible with "
                     "schedule.mode='buffered_async': pairwise masks "
                     "cancel only when a FIXED cohort's messages join "
                     "one combine — a buffered-async aggregation fires "
                     "on whichever M deltas arrive first, so mask "
                     "partners can land in different aggregations and "
                     "the dyadic-grid cancellation breaks (DESIGN.md §6)")
            _require(self.execution.exec_mode == "loop",
                     "execution.exec_mode='vmap' has no meaning under "
                     "schedule.mode='buffered_async': the fused graphs "
                     "stack a round's cohort, but the service has no "
                     "round barrier — each upload is an independent "
                     "per-client local update (the loop/reference "
                     "path); set exec_mode='loop'")
            _require(self.execution.mesh is None,
                     "execution.mesh shards the fused vmap graphs; the "
                     "buffered-async service aggregates its M-slot "
                     "buffer on the serving host — drop the mesh "
                     "(multi-host serving is a ROADMAP item)")
        mesh = self.execution.mesh
        if mesh is not None:
            # cohorts are NEVER silently repartitioned: an indivisible
            # mesh is refused at construction time, whatever exec_mode
            # (the mesh is part of the scenario's declared shape)
            L = self.data.num_clients
            k = min(self.schedule.clients_per_round or L, L)
            _require(k % mesh.data == 0,
                     f"execution.mesh data={mesh.data} does not divide "
                     f"the cohort width K={k} (schedule.clients_per_round"
                     f" or data.num_clients) — cohorts are never "
                     "silently repartitioned; resize K or the mesh")
            _require(L % mesh.data == 0,
                     f"execution.mesh data={mesh.data} does not divide "
                     f"the registered-client count L={L} "
                     "(data.num_clients) — the (L, ...) per-client state "
                     "trees shard over the same axis; resize L or the "
                     "mesh")

    def _refuse_secure_under_scan(self) -> None:
        """The engine's cohort schedule (``engine.scan_clients``) on this
        spec's parameter bytes, cohort width and the default device's
        free memory: pairwise masks span the cohort, so ``secure`` is
        refused where the round would scan its clients."""
        ex = self.execution
        if ex.exec_mode != "vmap" or ex.mesh is not None:
            return
        import jax
        from repro.core.engine import device_free_bytes, scan_clients
        if self.model.family == "lm":
            from repro.models.transformer import init_params
        else:
            from repro.core.ntm.prodlda import init_params
        cfg = self.to_model_config()
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        nbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(shapes))
        L = self.data.num_clients
        k = min(self.schedule.clients_per_round or L, L)
        _require(not scan_clients(nbytes, k,
                                  device_free_bytes(jax.devices()[0])),
                 "the 'secure' transform is refused under the client-scan "
                 f"round: the vmapped cohort ({k} clients of "
                 f"{nbytes / 1e9:.3g} GB parameters) does not fit this "
                 "device, and pairwise masks span the cohort — they "
                 "cancel only in one stacked combine")

    # -- resolved (cross-section) defaults --------------------------------
    @property
    def resolved_data_seed(self) -> int:
        return self.data.seed if self.data.seed is not None \
            else self.execution.seed

    @property
    def resolved_sampling_seed(self) -> int:
        return self.schedule.sampling_seed \
            if self.schedule.sampling_seed is not None \
            else self.execution.seed

    @property
    def resolved_shared_topics(self) -> int:
        return self.data.shared_topics if self.data.shared_topics is not None \
            else max(self.model.topics // 5, 1)

    @property
    def resolved_seq_len(self) -> int:
        """Tokens per federated LM document (model.seq_len, default 32)."""
        return self.model.seq_len or 32

    @property
    def resolved_buffer_size(self) -> int:
        """Buffered-async aggregation threshold M (schedule.buffer_size,
        0 = the cohort width K — the M=K default is the sync-equivalence
        anchor, DESIGN.md §6)."""
        L = self.data.num_clients
        k = min(self.schedule.clients_per_round or L, L)
        return self.schedule.buffer_size or k

    @property
    def resolved_staleness_policy(self) -> str:
        """Delta-discount policy under buffered_async
        (schedule.staleness_policy, '' = 'exponential' — the straggler
        ring's decay**age semantics)."""
        return self.schedule.staleness_policy or "exponential"

    # -- compilation to the engine's config objects -----------------------
    def to_model_config(self) -> ModelConfig:
        if self.model.family == "lm":
            return self._to_lm_model_config()
        return ModelConfig(name=self.name or "federation-spec", kind=NTM,
                           vocab_size=self.model.vocab,
                           num_topics=self.model.topics,
                           ntm_hidden=(self.model.hidden, self.model.hidden))

    def _to_lm_model_config(self) -> ModelConfig:
        """The arch's CPU-scale ``reduced()`` config with the spec's
        size overrides — the federated analogue of the launcher's
        ``--reduced`` path, so every registry family lowers the same
        way it does in the arch smoke tests.  ``model.published`` starts
        from the published config and takes the chip's share: depth,
        experts held (``ep_size``) and the vocabulary slice."""
        from repro.configs import get_config
        m = self.model
        if m.published:
            cfg = get_config(m.arch)
            kw = {"name": self.name or f"fed-{m.arch}",
                  "vocab_size": m.vocab,
                  "num_layers": m.layers or cfg.num_layers,
                  "max_seq_len": max(cfg.max_seq_len,
                                     self.resolved_seq_len + 1)}
            if m.ep_size:
                kw["moe"] = dataclasses.replace(cfg.moe, ep_size=m.ep_size)
            return dataclasses.replace(cfg, **kw)
        cfg = get_config(m.arch).reduced()
        kw: Dict[str, Any] = {
            "name": self.name or f"fed-{m.arch}",
            "vocab_size": m.vocab,
            # documents are seq_len+1 tokens (inputs + shifted labels)
            "max_seq_len": max(cfg.max_seq_len, self.resolved_seq_len + 1),
        }
        if m.layers:
            kw["num_layers"] = m.layers
        if m.width:
            heads = max(m.width // 64, 1)
            kw.update(d_model=m.width, d_ff=m.width * 2, num_heads=heads,
                      head_dim=64,
                      num_kv_heads=heads
                      if cfg.num_kv_heads >= cfg.num_heads
                      else max(1, heads // 2))
        return dataclasses.replace(cfg, **kw)

    def to_federated_config(self) -> FederatedConfig:
        t = self.transforms
        return FederatedConfig(
            num_clients=self.data.num_clients,
            learning_rate=self.execution.learning_rate,
            max_rounds=self.schedule.rounds,
            rel_tol=self.execution.rel_tol,
            dp_noise_multiplier=t.dp_noise_multiplier,
            dp_clip_norm=t.dp_clip_norm,
            message_precision=t.precision,
            compression_topk=t.compression_topk)

    def to_round_config(self) -> RoundConfig:
        s = self.schedule
        return RoundConfig(
            exec_mode=self.execution.exec_mode,
            clients_per_round=s.clients_per_round,
            sampling=s.sampling,
            sampling_seed=self.resolved_sampling_seed,
            local_epochs=s.local_epochs,
            server_optimizer=self.server_opt.name,
            server_lr=self.server_opt.lr,
            server_momentum=self.server_opt.momentum,
            server_beta2=self.server_opt.beta2,
            server_eps=self.server_opt.eps,
            straggler_prob=s.straggler_prob,
            max_staleness=s.max_staleness,
            staleness_decay=s.staleness_decay,
            transforms=self.transforms.names,
            pad_cohorts=self.execution.pad_cohorts,
            local_epochs_by_client=s.local_epochs_by_client,
            client_join_round=s.client_join_round,
            client_leave_round=s.client_leave_round,
            partition=self.data.partition.to_string(),
            kernel_backend=self.execution.kernel_backend,
            mesh_data=self.execution.mesh.data
            if self.execution.mesh is not None else 0)

    # -- dict / JSON round trip -------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        """Plain-JSON-types dict (tuples become lists, sections become
        mappings); the inverse of :meth:`from_dict`."""
        return _jsonify(dataclasses.asdict(self))

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "FederationSpec":
        """STRICT inverse of :meth:`to_dict` — unknown sections/keys and
        unsupported versions raise ``ValueError`` (a typo must never
        silently run a different scenario).  Omitted sections/keys take
        their defaults, so partial specs are valid."""
        if not isinstance(d, Mapping):
            raise ValueError("FederationSpec.from_dict needs a mapping, "
                             f"got {type(d).__name__}")
        known = set(_SECTIONS) | {"version", "name", "serving"}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(f"unknown top-level spec key(s) {unknown}; "
                             f"known: {sorted(known)}")
        version = d.get("version", SPEC_VERSION)
        if version != SPEC_VERSION:
            raise ValueError(
                f"FederationSpec version {version!r} is not supported by "
                f"this build (expected {SPEC_VERSION}); migrate the spec "
                "or update the repo")
        kw: Dict[str, Any] = {"version": version,
                              "name": d.get("name", "")}
        for sect, sect_cls in _SECTIONS.items():
            if sect in d:
                kw[sect] = _section_from_dict(sect_cls, d[sect], sect)
        if "serving" in d:
            kw["serving"] = ServingSpec.from_value(d["serving"])
        return cls(**kw)

    def to_json(self, *, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "FederationSpec":
        try:
            d = json.loads(s)
        except json.JSONDecodeError as e:
            raise ValueError(f"FederationSpec JSON does not parse: {e}") \
                from None
        return cls.from_dict(d)

    def save(self, path: str) -> str:
        """Atomic JSON write (tmp + rename, trailing newline)."""
        return atomic_write(path, lambda f: f.write(self.to_json() + "\n"))

    @classmethod
    def load(cls, path: str) -> "FederationSpec":
        try:
            with open(path) as f:
                text = f.read()
        except OSError as e:
            raise ValueError(f"cannot read spec file {path!r}: {e}") \
                from None
        try:
            return cls.from_json(text)
        except ValueError as e:
            raise ValueError(f"spec file {path!r}: {e}") from None


def _jsonify(v):
    if isinstance(v, dict):
        return {k: _jsonify(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonify(x) for x in v]
    return v


def _section_from_dict(cls, d, where: str):
    if isinstance(d, cls):
        return d
    if not isinstance(d, Mapping):
        raise ValueError(f"spec section {where!r} must be a mapping, got "
                         f"{type(d).__name__}")
    fields = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(d) - fields)
    if unknown:
        raise ValueError(f"unknown key(s) {unknown} in spec section "
                         f"{where!r}; known: {sorted(fields)}")
    kw = {}
    for fname, v in d.items():
        if cls is DataSpec and fname == "partition":
            v = PartitionSpec.from_value(v)
        elif cls is ExecutionSpec and fname == "mesh":
            v = MeshSpec.from_value(v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[fname] = v
    return cls(**kw)


# ---------------------------------------------------------------------------
# functional updates
# ---------------------------------------------------------------------------
def spec_replace(spec: FederationSpec,
                 overrides: Mapping[str, Any]) -> FederationSpec:
    """Dotted-path functional update over the spec tree.

    >>> spec_replace(spec, {"schedule.straggler_prob": 0.3,
    ...                     "data.partition": "dirichlet(0.3)",
    ...                     "name": "my-scenario"})

    Keys are either top-level (``name``, ``version``, or a whole section
    object) or ``section.field``; unknown paths raise ``ValueError``.
    The result re-validates (``__post_init__``), so an override can
    never produce an unchecked spec.
    """
    top: Dict[str, Any] = {}
    by_section: Dict[str, Dict[str, Any]] = {}
    serving_updates: Dict[str, Any] = {}
    for key, v in overrides.items():
        if "." in key:
            sect, _, fname = key.partition(".")
            if sect == "serving":
                serving_fields = {f.name
                                  for f in dataclasses.fields(ServingSpec)}
                if fname not in serving_fields:
                    raise ValueError(
                        f"unknown key {fname!r} in spec section "
                        f"'serving'; known: {sorted(serving_fields)}")
                serving_updates[fname] = v
                continue
            if sect not in _SECTIONS:
                raise ValueError(f"unknown spec section {sect!r} in "
                                 f"override {key!r}; known: "
                                 f"{sorted(set(_SECTIONS) | {'serving'})}")
            by_section.setdefault(sect, {})[fname] = v
        elif key == "serving":
            top[key] = ServingSpec.from_value(v)
        elif key in _SECTIONS or key in ("name", "version"):
            top[key] = v
        else:
            raise ValueError(f"unknown spec override {key!r}; use "
                             "'section.field' dotted paths or one of "
                             f"{sorted(set(_SECTIONS) | {'name', 'version', 'serving'})}")
    kw = dict(top)
    if serving_updates:
        # build on the whole-section override if one rode along, else on
        # the spec's current serving section; a nested update on a spec
        # without one creates the section (ServingSpec defaults + updates)
        base_serving = top.get("serving", spec.serving)
        kw["serving"] = ServingSpec(**serving_updates) \
            if base_serving is None \
            else dataclasses.replace(base_serving, **serving_updates)
    for sect, updates in by_section.items():
        cls = _SECTIONS[sect]
        fields = {f.name for f in dataclasses.fields(cls)}
        clean = {}
        mesh_updates: Dict[str, Any] = {}
        for fname, v in updates.items():
            if cls is ExecutionSpec and fname.startswith("mesh."):
                # nested dotted path: execution.mesh.<field>
                sub = fname[len("mesh."):]
                mesh_fields = {f.name for f in dataclasses.fields(MeshSpec)}
                if sub not in mesh_fields:
                    raise ValueError(
                        f"unknown key {sub!r} in spec section "
                        f"'execution.mesh'; known: {sorted(mesh_fields)}")
                mesh_updates[sub] = v
                continue
            if fname not in fields:
                raise ValueError(f"unknown key {fname!r} in spec section "
                                 f"{sect!r}; known: {sorted(fields)}")
            if cls is DataSpec and fname == "partition":
                v = PartitionSpec.from_value(v)
            elif cls is ExecutionSpec and fname == "mesh":
                v = MeshSpec.from_value(v)
            elif isinstance(v, list):
                v = tuple(v)
            clean[fname] = v
        if mesh_updates:
            # build on the whole-mesh override if one rode along, else
            # on the spec's current mesh; a nested update on a meshless
            # spec creates the section (MeshSpec defaults + updates)
            base_mesh = clean.get("mesh", getattr(spec, sect).mesh)
            clean["mesh"] = MeshSpec(**mesh_updates) if base_mesh is None \
                else dataclasses.replace(base_mesh, **mesh_updates)
        kw[sect] = dataclasses.replace(getattr(spec, sect), **clean)
    return dataclasses.replace(spec, **kw)
