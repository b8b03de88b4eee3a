"""Crash-safe file writes and DVV-tracked manifests for the segment log."""
from .manifest import Manifest, ShardRecord, resolve_manifest_siblings

__all__ = ["Manifest", "ShardRecord", "resolve_manifest_siblings"]
