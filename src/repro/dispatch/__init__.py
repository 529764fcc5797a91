"""Tile dispatcher: dependency-aware dispatch runtime for recurrent stacks.

See README.md in this directory for the mapping to SHARP §5–6.
"""
from repro.dispatch.executor import (PlanProgram, execute,
                                     prepare_decode_stack, stage_weights)
from repro.dispatch.planner import (Cell, DispatchPlan, ItemPlan, Slot,
                                    plan, plan_decode)
from repro.dispatch.workitem import WorkItem

__all__ = ["WorkItem", "plan", "plan_decode", "execute", "PlanProgram",
           "prepare_decode_stack", "stage_weights", "DispatchPlan",
           "ItemPlan", "Slot", "Cell"]
