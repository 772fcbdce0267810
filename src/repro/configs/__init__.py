"""Architecture configs: one module per assigned architecture."""
from repro.configs.base import ArchConfig, MoEConfig, MambaConfig  # noqa: F401
from repro.configs.registry import get_config, list_archs  # noqa: F401
