"""dedloc_tpu — a TPU-native collaborative deep-learning framework.

Re-imagines the capabilities of DeDLOC (arXiv 2106.10207; reference repo
yhn112/DeDLOC + hivemind 0.9.9) for JAX/XLA on TPU pod slices:

- In-slice data parallelism is a single ``pjit`` step whose gradient mean rides
  ICI collectives (replaces NCCL DDP *and* the intra-group butterfly for
  co-located chips).
- Cross-slice collaboration — a pure-Python asyncio DHT (Kademlia-style record
  store with expiration, subkeys and signed/validated records), DHT-driven
  matchmaking into bounded peer groups, fault-tolerant chunked all-reduce over
  TCP/DCN with fp16/uint8 wire compression and bandwidth-weighted partitioning,
  peer-to-peer state catch-up for late joiners, auxiliary bandwidth-donor peers
  and client-mode (firewalled) peers.

Layer map (mirrors SURVEY.md §1 of the reference analysis):

    transport  dedloc_tpu.dht.protocol      (asyncio TCP + msgpack framing)
    DHT        dedloc_tpu.dht               (routing, storage, validation)
    averaging  dedloc_tpu.averaging         (matchmaking, group all-reduce)
    optimizer  dedloc_tpu.collaborative     (CollaborativeOptimizer)
    training   dedloc_tpu.parallel          (pjit step, mesh, grad-accum,
                                             ring attention, ZeRO-1)
    kernels    dedloc_tpu.ops               (Pallas flash attention)
    models     dedloc_tpu.models            (ALBERT, ResNet-50/SwAV, five
                                             decoders over decoder.py)
    data       dedloc_tpu.data              (MLM+SOP, streaming, multicrop,
                                             tokenizer, prepare CLI)
    eval       dedloc_tpu.finetune          (NER/NCC drivers, linear probe)
    roles      dedloc_tpu.roles             (trainer / coordinator / aux /
                                             dht / swav / fleet)
    auth       dedloc_tpu.core.auth         (gated-run tokens + envelopes)
"""

__version__ = "0.1.0"

from dedloc_tpu.core.timeutils import get_dht_time  # noqa: F401
