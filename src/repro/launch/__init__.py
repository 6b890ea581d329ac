"""Launchers: the serving CLI and the persistent compile cache."""
