"""Deployment configs: ``sift1m``, the paper's own SIFT1M serving config, as
plain module constants."""
