"""Launchers: production mesh, train/serve/quantize CLIs."""
