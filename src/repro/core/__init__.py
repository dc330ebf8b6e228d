from repro.core.feddf import (FusionConfig, avg_logits_kl,
                              avg_logits_kl_pre, distill,
                              feddf_fuse_homogeneous,
                              feddf_fuse_heterogeneous,
                              feddf_fuse_heterogeneous_stacked,
                              feddf_fuse_stacked)
from repro.core.logit_bank import (PERSISTENT_BANK, TEACHER_FORWARDS,
                                   LogitBank, bank_for_fusion,
                                   build_logit_bank, resolve_bank)
from repro.core.server import (FLConfig, FLResult, RoundLog, run_federated,
                               run_federated_heterogeneous, run_rounds)
from repro.core.strategies import (ServerStrategy, available_strategies,
                                   get_strategy, register_strategy)
from repro.core.nets import Net, mlp, tiny_transformer
from repro.core.ensemble import ensemble_accuracy, ensemble_accuracy_stacked
from repro.core.dropworst import drop_worst, drop_worst_stacked
from repro.core.quantize import binarize, comm_bytes
