"""Distributed state placement: sharding rules, policies, and mesh roles.

``repro.dist.sharding`` is the only module that names mesh axes; everything
else (models, train/serve steps, the dry-run driver) talks to it through
named rules.
"""

from . import sharding

__all__ = ["sharding"]
