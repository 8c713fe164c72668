"""The mixers a `LayerKind` can name: ``LayerKind.mixer`` indexes `MIXERS`,
and "none" — a block that is a feed-forward alone — names no entry.  A new
mixer is a file beside this one that builds its `Mixer` (models/mixer.py), and
a line here."""

from typing import Dict

from torchft_tpu.models.attention import ATTENTION, CCA, MLA
from torchft_tpu.models.kda import KDA
from torchft_tpu.models.mamba import MAMBA2
from torchft_tpu.models.mixer import Mixer

MIXERS: Dict[str, Mixer] = {"attention": ATTENTION, "mla": MLA, "cca": CCA, "kda": KDA, "mamba2": MAMBA2}
