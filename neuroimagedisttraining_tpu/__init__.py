"""TPU-native federated learning framework for neuroimaging.

A ground-up JAX/XLA re-design of the capabilities of
riohib/NeuroImageDistTraining (a FedML fork for federated sex-classification
on the site-partitioned ABCD neuroimaging cohort): nine FL algorithms,
SNIP/ERK sparse training, 3D CNN model zoo, non-IID partitioning,
Byzantine-robust aggregation, and gossip topologies.

Design stance (see SURVEY.md §7): the reference simulates clients
*sequentially* in one Python loop with CPU weight averaging
(`fedml_api/standalone/*/\\*_api.py`). Here a federated round is a single
jitted SPMD program: every per-client quantity (params, optimizer momentum,
masks, RNG, data) is a pytree with a leading client axis, sharded over a
`clients` mesh axis; local SGD is a `lax.scan` vmapped over clients;
aggregation is a weighted reduction that XLA lowers to ICI collectives.
"""
import time as _time

# the span tree's first event (obs/trace.py: ``import_program``): this
# module's own import, on ``perf_counter_ns``'s clock. Nothing else is
# imported here, so it is the moment the program was first reached; what a
# process spent before it is the interpreter's and its other imports'
_IMPORT_START_NS = _time.perf_counter_ns()

__version__ = "0.1.0"

_IMPORT_END_NS = _time.perf_counter_ns()
