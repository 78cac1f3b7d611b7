"""The ``fleet`` kit: one resource type, one fused fleet epoch.

A configuration that names no kit runs this one.  It binds the
harness's single-tree deployment as it stands: the three-kind tenant
generator (``lcbench.workload``), the program built through
``make_fleet`` and driven by ``EpochRunner.epoch``
(``lcbench.deploy``), the plain numpy reference of one tree's epoch
(``lcbench.reference``) and the numbers ``correct`` compares
(``lcbench.compare``).
"""
from lcbench.compare import NAMES, readings  # noqa: F401
from lcbench.deploy import Deployment  # noqa: F401
from lcbench.reference import Reference  # noqa: F401
from lcbench.workload import make_params, tenant_table  # noqa: F401
