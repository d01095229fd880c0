"""The paper's own application: Ludwig D3Q19 binary-fluid benchmark.

Grid/production sizes follow the Ludwig GPU-scaling papers ([2][3] in the
paper): ~128³ per device.  The chip benchmark's configurations live in
``bench/configs/``; the production config is the dry-run / multi-pod
slab-decomposition cell.
"""
from dataclasses import dataclass

from repro.lb.params import LBParams


@dataclass(frozen=True)
class LudwigConfig:
    grid_shape: tuple
    params: LBParams = LBParams()
    vvl: int = 128
    backend: str = "xla"


# paper Fig. 1 benchmark scale (single device, CPU-measurable)
BENCH = LudwigConfig(grid_shape=(64, 64, 64))

# smoke scale
SMOKE = LudwigConfig(grid_shape=(8, 8, 8), vvl=32)

# production slab per 256-chip pod: X sharded 16-way, Y 16-way
PRODUCTION = LudwigConfig(grid_shape=(512, 512, 256))
