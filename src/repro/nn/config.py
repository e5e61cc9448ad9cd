"""CapsNet geometry configs (paper Table 1 / Table 7).

Geometry check against the paper (exact): with VALID padding,
  MNIST    28x28x1: conv16 k7 s1 -> 22x22; pcap k7 s2 -> 8x8x(16x4)
           -> 1024 input capsules  => caps layer 10x1024x6x4   (Table 7 "L")
           => 297.1k params = 1187.20 KB fp32                  (Table 2)
  smallNORB 32x32x2 (resized, as the paper's table sizes imply): conv32 k7
           -> 26x26; pcap k7 s2 -> 10x10 -> 1600 caps => 5x1600x6x4 ("M")
           => 295.6k params = 1182.34 KB fp32
  CIFAR-10 32x32x3: convs 32,32,64,64 k3 s1,1,2,2 -> 6x6; pcap k3 s2 ->
           2x2 -> 64 caps => 10x64x5x4 ("S") => 115.3k = 461.19 KB fp32
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class CapsNetConfig:
    name: str
    input_shape: tuple                     # (H, W, C)
    conv_filters: tuple                    # e.g. (16,) or (32,32,64,64)
    conv_kernels: tuple
    conv_strides: tuple
    pcap_caps: int = 16
    pcap_dim: int = 4
    pcap_kernel: int = 7
    pcap_stride: int = 2
    num_classes: int = 10
    caps_dim: int = 6
    routings: int = 3
    lr: float = 1e-3

    @property
    def conv_out_hw(self) -> tuple:
        h, w = self.input_shape[0], self.input_shape[1]
        for k, s in zip(self.conv_kernels, self.conv_strides):
            h = (h - k) // s + 1
            w = (w - k) // s + 1
        return h, w

    @property
    def pcap_out_hw(self) -> tuple:
        h, w = self.conv_out_hw
        k, s = self.pcap_kernel, self.pcap_stride
        return (h - k) // s + 1, (w - k) // s + 1

    @property
    def num_input_caps(self) -> int:
        h, w = self.pcap_out_hw
        return h * w * self.pcap_caps


MNIST = CapsNetConfig("capsnet_mnist", (28, 28, 1), (16,), (7,), (1,),
                      num_classes=10, caps_dim=6, lr=1e-3)
SMALLNORB = CapsNetConfig("capsnet_smallnorb", (32, 32, 2), (32,), (7,), (1,),
                          num_classes=5, caps_dim=6, lr=2.5e-4)
CIFAR10 = CapsNetConfig("capsnet_cifar10", (32, 32, 3), (32, 32, 64, 64),
                        (3, 3, 3, 3), (1, 1, 2, 2), pcap_kernel=3,
                        num_classes=10, caps_dim=5, lr=2.5e-4)
CAPSNET_CONFIGS = {c.name: c for c in (MNIST, SMALLNORB, CIFAR10)}


@dataclasses.dataclass(frozen=True)
class DeepCapsConfig:
    """DeepCaps (Rajasegaran et al., CVPR 2019, arXiv:1904.09546).

    A 3x3 SAME conv stem (`stem_filters`, relu), then one capsule cell
    per entry of `cells` (capsule types, atoms), each halving the grid,
    and `num_classes` class capsules of `caps_dim` routed from the last
    two cells' capsules (`CapsPipeline.from_deepcaps` builds it).  The
    CIFAR-10 model at 64x64x3: cells 32x4 at 32x32, 32x8 at 16x16, 8x8
    and 4x4 -> 512 + 2,048 = 2,560 capsules of 8D -> 10 x 32D."""
    name: str
    input_shape: tuple = (64, 64, 3)
    stem_filters: int = 128
    cells: tuple = ((32, 4), (32, 8), (32, 8), (32, 8))
    num_classes: int = 10
    caps_dim: int = 32
    routings: int = 3

    def cell_hw(self, n: int) -> tuple:
        """The grid of cell `n` (1-based): each halves it, SAME padded."""
        h, w = self.input_shape[0], self.input_shape[1]
        for _ in range(n):
            h, w = -(-h // 2), -(-w // 2)
        return h, w

    @property
    def num_input_caps(self) -> int:
        """Capsules of the last two cells, which the class layer reads."""
        n = len(self.cells)
        return sum(self.cell_hw(k)[0] * self.cell_hw(k)[1]
                   * self.cells[k - 1][0] for k in (n, n - 1))


DEEPCAPS_CIFAR10 = DeepCapsConfig("deepcaps_cifar10")
