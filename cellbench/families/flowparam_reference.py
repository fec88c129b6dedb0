"""The plain reference of a deployment whose resources carry a flow rule and
a hot-parameter rule at once: what a token server must answer to a *call*.

Upstream runs a call through ``ParamFlowSlot`` (order -3000) and then
``FlowSlot`` (order -2000): in cluster mode the first asks the token server
``requestParamToken`` for the call's parameter value (its caller), the second
``requestToken`` for the resource, and a call the first refused never reaches
the second. So a call on a resource is

    param check   only where the resource has a param rule: the caller's
                  exact windowed count against its threshold
                  (``hotparam_reference``: a dictionary per (rule, caller))
    flow check    only if the param check passed or there was none: the
                  namespace guard, then the flow's exact windowed count
                  (``flow_reference``)

and a call the param check refused takes no flow token and no request of the
namespace guard. The two states never touch: a param request changes no
flow's count and a flow request no caller's, whatever numbers their rules
carry. The namespace guard counts flow requests only, which is this
program's departure from upstream (the configuration's ``assumed`` says so);
the reference states the program's semantics, as the other families' do.

Pure Python over the two sibling references: imports nothing of the program.
"""

from __future__ import annotations

from cellbench.deploy import OK
from cellbench.families import flow_reference, hotparam_reference


class Reference:
    def __init__(self, flow_rules, param_rules, param_rule_of, ns_max_qps,
                 flow_window, param_window):
        """``flow_rules``: ``{flow id: (count, namespace, behaviour)}``;
        ``param_rules``: ``{rule id: (count, {caller: threshold})}``;
        ``param_rule_of``: ``{flow id: its param rule's id}`` for the
        resources that carry one; the windows as ``(bucket_ms,
        n_buckets)``."""
        self.flow = flow_reference.Reference(flow_rules, ns_max_qps,
                                             *flow_window)
        self.param = hotparam_reference.Reference(param_rules, *param_window)
        self.param_rule_of = dict(param_rule_of)

    def call(self, t_ms: int, resource: int, acquire: int, caller: int):
        """One call arriving at ``t_ms``: ``(param status or None, flow
        status or None)``; None where that check was not made."""
        rule = self.param_rule_of.get(resource)
        asked = None
        if rule is not None:
            asked = self.param.decide(t_ms, rule, acquire, [caller])
            if asked != OK:
                return asked, None
        return asked, self.flow.decide(t_ms, resource, acquire)[0]

    def calls(self, t_ms: int, resources, acquires, callers) -> list:
        """Calls in order, all at ``t_ms``."""
        return [self.call(t_ms, int(r), int(a), int(c))
                for r, a, c in zip(resources, acquires, callers)]

    def flow_frame(self, t_ms: int, flow_ids, acquires) -> list:
        """The statuses of a frame of flow requests, decided in order."""
        return self.flow.decide_frame(t_ms, flow_ids, acquires)[0]

    def param_frame(self, t_ms: int, rules, acquires, values) -> list:
        """The statuses of a frame of param requests, decided in order."""
        return self.param.decide_all(t_ms, rules, acquires, values)


def for_deployment(dep) -> Reference:
    e, p = dep.spec["engine"], dep.spec["param"]
    return Reference(
        {fid: (count, ns, behaviour)
         for fid, count, ns, behaviour in dep.flow_rules()},
        {rule: (count, dict(items))
         for rule, count, items, _ns in dep.param_rules()},
        dep.param_rule_of(), dep.flow.ns_max_qps,
        (int(e["bucket_ms"]), int(e["n_buckets"])),
        (int(p["bucket_ms"]), int(p["n_buckets"])))
