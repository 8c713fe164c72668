"""torchft_tpu.semisync — the streaming semi-sync (DiLoCo) data plane.

Makes communication-efficient outer-loop synchronization first-class for
the cross-region / low-bandwidth links torchft targets with LocalSGD:
the outer state is fragmented on the shared bucket planner, each
fragment's pseudogradient round streams in the background of inner steps
over the striped multi-lane ring (``ring2d`` at high group counts), the
wire rides an **int8 + error-feedback** codec (bf16/f32 fallback knob),
and the per-fragment outer optimizer applies only after the commit vote —
a failed sync can never corrupt the model, the backup, or the outer
state.

Layout:
  fragments.py  fragment planning (ddp.plan_buckets underneath) + slots
  codec.py      int8+EF / bf16 / f32 / auto wire preparation (jitted)
  engine.py     the background fragment-sync worker
  diloco.py     StreamingDiLoCo (the user-facing algorithm)
  metrics.py    tpuft_semisync_* Prometheus exposition

``torchft_tpu.local_sgd.DiLoCo`` is preserved as a thin blocking wrapper
over this engine; see docs/architecture.md "Streaming semi-sync data
plane".
"""

from torchft_tpu.semisync.codec import CODECS, FragmentCodec, make_codec
from torchft_tpu.semisync.diloco import StreamingDiLoCo
from torchft_tpu.semisync.engine import SyncEngine
from torchft_tpu.semisync.fragments import (
    DEFAULT_FRAGMENT_BYTES,
    Fragment,
    FragmentPlan,
)
from torchft_tpu.semisync.metrics import SemiSyncMetrics

__all__ = [
    "StreamingDiLoCo",
    "SyncEngine",
    "Fragment",
    "FragmentPlan",
    "FragmentCodec",
    "make_codec",
    "SemiSyncMetrics",
    "CODECS",
    "DEFAULT_FRAGMENT_BYTES",
]
