from repro.configs.base import ANNSConfig  # noqa: F401
