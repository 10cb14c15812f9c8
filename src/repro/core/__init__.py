"""The paper's contribution: gFedNTM — federated neural topic modeling."""
from repro.core import aggregation, engine, protocol, rounds, vocab  # noqa: F401,E501
from repro.core.aggregation import (  # noqa: F401
    SERVER_OPTIMIZERS, ServerOptimizer, get_server_optimizer)
from repro.core.engine import FederationEngine, combine_arrivals  # noqa: F401,E501
from repro.core.transforms import (  # noqa: F401
    TRANSFORMS, TransformCtx, build_transforms)
from repro.core.protocol import (  # noqa: F401
    ClientState, FedAvgTrainer, FederatedTrainer,
    make_federated_train_step, param_delta, train_centralized,
    train_non_collaborative, weighted_global_loss)
from repro.core.rounds import RoundEngine, RoundScheduler  # noqa: F401
from repro.core.vocab import (  # noqa: F401
    Vocabulary, consensus_token_map, merge_vocabularies, reindex_bow)
