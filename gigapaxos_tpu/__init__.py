"""gigapaxos_tpu — a TPU-native group-scalable replicated state machine framework.

A brand-new implementation of the capabilities of GigaPaxos (reference:
``/root/reference``, ``src/edu/umass/cs/gigapaxos/PaxosManager.java:104-119``):
millions of independent Paxos consensus groups per node with on-demand
creation, pausing, persistent logging/checkpointing, failure detection,
coordinator election, and a reconfiguration layer that migrates replica sets
at runtime — all behind a ``Replicable{execute, checkpoint, restore}`` app SPI.

Unlike the reference's object-per-group Java event machines over custom TCP
NIO, the core here is a **batched JAX/XLA engine**: the acceptor and
coordinator state of *all* groups lives as HBM-resident ``[G]`` / ``[G, W]``
int32 arrays, and prepare/accept/decide for every group advance together as
vectorized ops inside a single jitted step.  Inter-replica Paxos traffic is
one ``all_gather`` of a packed int32 state blob over a 'replica' mesh axis
(ICI), not per-group point-to-point messages.

Layout (mirrors SURVEY.md §7):
  utils/       config flags, disk-backed maps                 (ref: utils/)
  obs/         structured logging, per-request tracing,
               engine metrics registry                        (ref: j.u.logging + RequestInstrumenter)
  interfaces/  Replicable app SPI, Request types              (ref: gigapaxos/interfaces/)
  packets/     wire packets + tensor packing                  (ref: paxospackets/)
  ops/         the batched consensus kernels                  (ref: PaxosAcceptor/Coordinator)
  parallel/    mesh construction, shard_map SPMD step         (ref: nio/ multicast)
  storage/     journal + checkpoint durability                (ref: SQLPaxosLogger)
  net/         host transport (client/control plane over DCN) (ref: nio/)
  models/      example Replicable apps                        (ref: examples/)
  reconfiguration/  control plane: create/delete/migrate RSMs (ref: reconfiguration/)
  clients/     async clients                                  (ref: PaxosClientAsync)
"""

__version__ = "0.1.0"
