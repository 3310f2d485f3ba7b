# TD-Orch reproduction: task-data orchestration (repro.core), the §4/§5 case
# studies (repro.kvstore, repro.graph), and the JAX/Pallas production stack
# (repro.models, repro.launch, repro.kernels, repro.runtime).
