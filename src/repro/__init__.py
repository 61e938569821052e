"""Jigsaw reproduction package."""
