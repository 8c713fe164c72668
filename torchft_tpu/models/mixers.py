"""The mixers a `LayerKind` can name: ``LayerKind.mixer`` indexes `MIXERS`,
and "none" — a block that is a feed-forward alone — names no entry.  A new
mixer is a file beside this one that builds its `Mixer` (models/mixer.py), and
a line here.  Today: softmax attention in three forms
(models/attention.py), Kimi Delta Attention (models/kda.py), Gated DeltaNet
(models/gdn.py), Mamba-2 (models/mamba.py)."""

from typing import Dict

from torchft_tpu.models.attention import ATTENTION, CCA, MLA
from torchft_tpu.models.gdn import GDN
from torchft_tpu.models.kda import KDA
from torchft_tpu.models.mamba import MAMBA2
from torchft_tpu.models.mixer import Mixer

MIXERS: Dict[str, Mixer] = {"attention": ATTENTION, "mla": MLA, "cca": CCA, "kda": KDA, "gdn": GDN,
                            "mamba2": MAMBA2}
