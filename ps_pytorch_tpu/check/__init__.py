"""pscheck — jaxpr-level contract checking for the parallel schemes.

pslint (ps_pytorch_tpu/lint) guards the SOURCE TEXT; pscheck guards what
XLA is actually asked to do: it traces each scheme's real step function
(CPU-only, abstract inputs, nothing executes) and walks the jaxpr to
verify the communication contracts ARCHITECTURE §1-§6b claim — every
axis carries its collective (PSC101), gradient reductions feed the
optimizer (PSC102), compressed wires stay int8 (PSC103), per-collective
wire bytes round-trip against runs/comm_contract.json (PSC104),
donation survives lowering (PSC105), bucketed wires stay fused — no
more gradient-path collectives than the declared bucket plan allows
(PSC106) — the serving hot path stays collective-free with an
honest KV storage dtype (PSC107), and adaptive-mask configs keep their
grad-reduce declaration and byte envelope (PSC108), pipelined
configs move exactly their serial twin's bytes with a real per-bucket
dispatch (PSC109), and adaptive configs name a real host-consensus
point for their traced count — checked against pslint's consensus
inventory (PSC110, the static half of PSL007's divergence guarantee).

PSC111-114 are the psnumerics rules (check/numerics.py): a precision-
flow analysis over the same traced jaxpr proves the quantized wire's
numerics — scale provenance (PSC111), error-feedback closure (PSC112),
integer-accumulation capacity from the traced axis sizes (PSC113), and
no silent downcast on the update path (PSC114). They run for every spec
declaring a NumericsPolicy; rule subsets via ``--select PSC1xx,...``.

Entry points: ``python -m ps_pytorch_tpu.check``, ``tools/check.sh``,
and the tier-1 gate in tests/test_check.py.
"""

from .contracts import (
    AdaptivePolicy,
    Built,
    ContractSpec,
    DonationSpec,
    FusionSpec,
    GradReduce,
    NarrowingAllowance,
    NumericsPolicy,
    OverlapPolicy,
    ServePolicy,
    WireAllowance,
    WirePolicy,
    get_contracts,
)
from .numerics import NumericsReport, analyze_numerics
from .core import (
    CheckFinding,
    TraceResult,
    load_contract,
    run_checks,
    to_contract_json,
    trace_registry,
    trace_spec,
    write_contract,
)
from .opcount import update_path_op_count
from .rules import RULE_IDS
from .walker import Collective, collect_collectives, summarize

__all__ = [
    "AdaptivePolicy",
    "Built",
    "CheckFinding",
    "Collective",
    "ContractSpec",
    "DonationSpec",
    "FusionSpec",
    "GradReduce",
    "NarrowingAllowance",
    "NumericsPolicy",
    "NumericsReport",
    "OverlapPolicy",
    "RULE_IDS",
    "ServePolicy",
    "TraceResult",
    "WireAllowance",
    "WirePolicy",
    "analyze_numerics",
    "collect_collectives",
    "get_contracts",
    "load_contract",
    "run_checks",
    "summarize",
    "to_contract_json",
    "trace_registry",
    "trace_spec",
    "update_path_op_count",
    "write_contract",
]
