"""Typed capsule-layer API: one pipeline for float forward, PTQ
calibration, and int8 inference.  See README.md in this package."""
from repro.nn.backend import (BACKENDS, JnpBackend,  # noqa: F401
                              PallasBackend, get_backend)
from repro.nn.config import (CAPSNET_CONFIGS, CIFAR10,  # noqa: F401
                             DEEPCAPS_CIFAR10, MNIST, SMALLNORB,
                             CapsNetConfig, DeepCapsConfig)
from repro.nn.layers import (CapsAdd, CapsConcat,  # noqa: F401
                             CapsLayer, CapsuleRouting, ConvCaps3D,
                             PrimaryCaps, QuantConv2D)
from repro.nn.pipeline import CapsPipeline, QuantCapsNet  # noqa: F401
from repro.nn.plans import (ConvPlan, LocalRoutingPlan,  # noqa: F401
                            MergePlan, PipelinePlan, PrimaryCapsPlan,
                            RoutingPlan, TapStats)
from repro.nn.variants import (REGISTRY, OpVariant,  # noqa: F401
                               VariantRegistry, VariantSet,
                               all_variant_sets)
