from repro.data.synthetic import clustered_vectors  # noqa: F401
