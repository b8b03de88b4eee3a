"""Launchers: the persistent compile cache and the store workload CLI."""
