"""The block architectures the harness knows, one module each, found by a
configuration's ``model_type`` (``harness.spec.load_arch``)."""
