"""The CSR tile path's configuration (DESIGN.md §3.1).

The daemon runs one CSR tile path on every backend: per-edge ``take``
gathers in XLA, then the fused Pallas tile kernel (Gen + one-hot/masked
Merge), then the cross-tile combine.  Its one tuning left is the edge
tile (and the hub threshold that degree bucketing derives from it).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class CSRConfig:
    """The CSR tile path's parameters.

    Attributes:
      edge_tile: edges per tile (ET); also the degree-bucketing hub
        threshold unless ``hub_threshold`` overrides it.
      hub_threshold: in-degree above which a row streams across tiles;
        None means ``edge_tile``.
      lowering, merge, gather: the path's fixed names ("pallas",
        "onehot", "take"), kept so a config that spells them still
        reads; any other value raises.
    """

    edge_tile: int = 512
    lowering: str = "pallas"
    merge: str = "onehot"
    gather: str = "take"
    hub_threshold: int | None = None

    def __post_init__(self):
        for field, want in (("lowering", "pallas"), ("merge", "onehot"),
                            ("gather", "take")):
            got = getattr(self, field)
            if got != want:
                raise ValueError(
                    f"CSRConfig.{field}={got!r}: the CSR tile path is "
                    f"pallas/onehot/take, {field} must be {want!r}")

    @property
    def label(self) -> str:
        return f"{self.lowering}/{self.merge}/{self.gather}/et{self.edge_tile}"
