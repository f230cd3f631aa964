"""Launchers: mesh factories and the persistent compilation cache."""
